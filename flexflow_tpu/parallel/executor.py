"""PCG executor: lowers a parallelized PCG to one jitted XLA program.

This replaces the reference's entire execution stack — Legion IndexLaunchers
per op (src/ops/*.cc forward()/backward()), FFMapper placement
(src/mapper/mapper.cc), Realm data movement, and NCCL gradient allreduce
(src/runtime/optimizer.cc nccl_update_task) — with a single SPMD program:

  * op forwards run in topo order inside one traced function,
  * ParallelTensor shardings become with_sharding_constraint, so the XLA
    partitioner inserts the collectives the reference's parallel ops and
    NCCL calls perform,
  * jax.grad generates every backward task,
  * the optimizer update is fused into the same program (the reference's
    overlap_backward_update, config.h:133, is automatic here),
  * Legion trace replay (begin/end_trace) ≈ the jit cache.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import losses as losses_mod
from ..core.initializers import get_initializer
from ..core.metrics import Metrics
from ..core.optimizers import Optimizer
from ..ff_types import (
    CompMode,
    DataType,
    LossType,
    OperatorType,
    RegularizerMode,
)
from ..ops.registry import FwdCtx, get_op_def
from ..pcg.graph import Graph
from ..pcg.op import PCGOp
from .mesh import pspec_for_parallel_tensor, sharding_for_parallel_tensor
from . import parallel_ops as par_ops

# Ops whose forward allocates large internal residuals worth recomputing in
# the backward (reference has no equivalent — cuDNN owns these residuals;
# XLA lets us trade FLOPs for HBM via jax.checkpoint). MoE ops are excluded:
# their forward appends aux losses, which must trace exactly once.
_REMAT_OPS = frozenset({OperatorType.OP_MULTIHEAD_ATTENTION})


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GuardState:
    """Device-resident step-guard counters (runtime/resilience.py
    StepGuardConfig): dynamic loss scale + skip bookkeeping, advanced
    inside the jitted train step so guarded training stays one dispatch."""

    loss_scale: jax.Array        # f32 scalar
    good_steps: jax.Array        # i32: consecutive finite steps (regrowth)
    consecutive_skips: jax.Array  # i32: fit() hard-fails past the config max
    total_skips: jax.Array       # i32: run-lifetime skipped steps


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """All device-resident state of a compiled model."""

    params: Dict[str, Dict[str, jax.Array]]
    opt_state: Any
    step: int = 0
    # non-trainable cross-batch buffers (BN running stats, Cache op);
    # keyed op.name -> buffer name -> array
    net_state: Dict[str, Dict[str, jax.Array]] = dataclasses.field(
        default_factory=dict
    )
    # step-guard counters; None when the guard is off (the default)
    guard: Optional[GuardState] = None


def global_grad_norm(grads) -> jax.Array:
    """L2 norm over every gradient leaf, accumulated in f32 (bf16 grads
    would overflow the squares). NaN/Inf anywhere in any leaf surfaces
    here as a non-finite norm — one scalar finiteness check covers the
    whole gradient pytree."""
    leaves = [g for g in jax.tree_util.tree_leaves(grads) if g is not None]
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    total = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    return jnp.sqrt(total)


def _count_trace(program: str) -> None:
    """Called from inside a traced body: a Python side effect, so it runs
    once per TRACE of that program and never per execution. The count
    says which program was built again (a new shape, a state whose
    leaves changed type)."""
    from .. import obs

    obs.count("ff_program_traces_total",
              help="times a jitted program's body was traced",
              program=program)


def _tree_select(pred, new, old):
    """Leafwise where(pred, new, old) tolerating None leaves (SGD without
    momentum keeps {"v": None}) — used to carry params/opt state through
    unchanged on a skipped step."""
    def sel(n, o):
        if n is None or o is None:
            return n if o is None else o
        return jnp.where(pred, n, o)

    return jax.tree_util.tree_map(
        sel, new, old, is_leaf=lambda x: x is None
    )


def truncate_labels(labels, logits, seq_length: int = 0):
    """Per-iteration seq truncation must hit the LABELS too: with
    forward(seq_length=N) the logits lose positions, and a loss/metric
    against full-length labels shape-errors. Slices every label axis that
    is LONGER than the logits' (seq axes shrink; a sparse label's trailing
    1 stays — it's never longer than the vocab axis)."""
    if labels.ndim != logits.ndim:
        return labels
    for ax in range(1, labels.ndim):
        if labels.shape[ax] > logits.shape[ax]:
            labels = jax.lax.slice_in_dim(labels, 0, logits.shape[ax], axis=ax)
    return labels


class PCGExecutor:
    """Builds and caches the jitted step functions for a PCG."""

    def __init__(
        self,
        graph: Graph,
        mesh: Mesh,
        optimizer: Optimizer,
        loss_type: LossType,
        metrics: Metrics,
        *,
        compute_dtype=None,
        grad_dtype=None,
        seed: int = 0,
        input_order: Optional[List] = None,
        remat: bool = False,
        constants: Optional[Dict] = None,
        plan_cost_model=None,
        overlap_grad_sync: bool = False,
    ):
        self.graph = graph
        self.mesh = mesh
        # cost oracle for pipeline stage planning (the same calibrated
        # model the strategy search uses; None = default v5e constants)
        self._plan_cost_model = plan_cost_model
        self.remat = remat
        # guid -> (ParallelTensor, python float OR baked np.ndarray):
        # materialized as jnp.full / jnp.asarray at trace time, excluded
        # from batch inputs (reference: flexflow_constant_create,
        # flexflow_cffi.py:941)
        self.constants = constants or {}
        self.optimizer = optimizer
        self.loss_type = loss_type
        self.loss_fn = losses_mod.get_loss_fn(loss_type)
        self.metrics = metrics
        self.compute_dtype = compute_dtype
        # Gradient storage dtype (None = param dtype). bf16 under mixed
        # precision: converts fuse into the grad matmuls' epilogues, so
        # grads hit HBM (and any cross-chip reduction) at half width —
        # the AMP recipe (half-width grads + f32 master weights). The
        # optimizer update reads them back with f32 promotion.
        self.grad_dtype = grad_dtype
        self.seed = seed
        self.topo = graph.topo_order()
        # loop regions (FFModel.loop): each runs as one body over its steps
        self.loops = graph.loops()
        self._loop_of = {op.guid: reg for reg in self.loops for op in reg.ops}
        for op in (o for o in self.topo if o.guid in self._loop_of):
            if not op.is_parallel_op \
                    and get_op_def(op.op_type).forward_stateful is not None:
                raise NotImplementedError(
                    f"{op.name}: an op with cross-batch buffers cannot run "
                    "inside a loop region")
        # an op's index among COMPUTE ops, which its rng folds in
        self._compute_idx = {
            op.guid: i for i, op in enumerate(
                o for o in self.topo if not o.is_parallel_op)}
        # User-facing input order is tensor *creation* order (the order of
        # FFModel.create_tensor calls), not graph consumption order —
        # multi-input models (DLRM dense+sparse, enc-dec) depend on it.
        self.input_pts = (
            list(input_order) if input_order is not None else graph.input_tensors()
        )
        outs = graph.output_tensors()
        assert outs, "graph has no output tensor"
        self.logits_pt = outs[-1]
        # Comm/compute-overlapped gradient sync (the reference's
        # overlap_backward_update, config.h:133): decompose the implicit
        # data-parallel grad all-reduce into per-weight reduce-scatter +
        # sharded optimizer update + all-gather of the updated params
        # (set_overlap_grad_sync / config.overlap_backward_update).
        self.overlap_grad_sync = overlap_grad_sync
        self._overlap_spec_cache = None
        # NaN/Inf step guard (runtime/resilience.py StepGuardConfig);
        # None = unguarded step (the default). Changing it invalidates
        # the cached train step (set_step_guard).
        self.step_guard = None
        # extra per-step outputs folded into the metric partials
        # (set_step_metrics; telemetry feed, e.g. "grad_norm")
        self.step_metrics: tuple = ()
        self._train_step = None
        self._train_step_nodonate = None
        self._train_scan = None
        self._grad_step = None
        self._eval_step = None
        self._fwd = None
        self._decode_builds = {}
        self._seq_len_cache = {}  # ("fwd"|"grad", seq_length) -> jitted fn
        # generalized pipeline: a pipe mesh axis with no block-stack op
        # means the graph itself must be stage-partitioned (CNNs,
        # non-uniform transformers — parallel/pipeline.py gpipe_pcg)
        self.pipeline_plan = None
        pipe = mesh.shape.get("pipe", 1) if mesh is not None else 1
        if pipe > 1 and not any(
            op.op_type == OperatorType.OP_BLOCK_STACK for op in self.topo
        ):
            if self.loops:
                raise NotImplementedError(
                    "a loop region cannot be cut into pipeline stages")
            self.pipeline_plan = self._plan_pcg_pipeline(pipe)

    # -- generalized pipeline planning --------------------------------------
    def _plan_pcg_pipeline(self, n_stages: int):
        """Partition the compute graph into `n_stages` contiguous stages
        balanced by analytic op cost ("the search proposes the cut"), and
        describe each cut's boundary tensors. Falls back to None (warn)
        when the graph can't be pipelined exactly."""
        import warnings

        from ..search.cost_model import op_bytes, op_flops
        from ..search.machine_model import MachineModel
        from .pipeline import PcgPipelinePlan, balanced_linear_partition

        ops = [o for o in self.topo if not o.is_parallel_op]
        if len(ops) < n_stages:
            warnings.warn("pipeline: fewer compute ops than stages — "
                          "running unpipelined")
            return None
        for op in ops:
            d = get_op_def(op.op_type)
            if d.state_spec is not None or op.op_type in (
                OperatorType.OP_GROUP_BY, OperatorType.OP_AGGREGATE,
                OperatorType.OP_AGG_SPEC, OperatorType.OP_CACHE,
            ):
                warnings.warn(
                    f"pipeline: {op.op_type.name} (stateful/aux-loss op) "
                    "can't cross the GPipe schedule — running unpipelined"
                )
                return None
        if self._plan_cost_model is not None:
            from ..pcg.machine_view import MachineView

            v1 = MachineView(start_device_id=0, dim=(1,), stride=(1,))
            costs = [
                self._plan_cost_model.measure_operator_cost(o, v1).total_time
                for o in ops
            ]
        else:
            machine = MachineModel()
            costs = [
                machine.compute_cost(op_flops(o), op_bytes(o)) for o in ops
            ]
        bounds = balanced_linear_partition(costs, n_stages)
        stages = [ops[bounds[i]:bounds[i + 1]]
                  for i in range(len(bounds) - 1)]
        stages = [s for s in stages if s]
        if len(stages) < n_stages:
            warnings.warn("pipeline: degenerate stage partition — "
                          "running unpipelined")
            return None

        stage_of = {}
        for si, sops in enumerate(stages):
            for o in sops:
                stage_of[o.guid] = si
        # parallel ops (degree bookkeeping) are identity device-local:
        # resolve their outputs back to the producing compute tensor
        alias: Dict[int, int] = {}
        for op in self.topo:
            if op.is_parallel_op:
                src = alias.get(op.inputs[0].guid, op.inputs[0].guid)
                for t in op.outputs:
                    alias[t.guid] = src

        def resolve(g):
            return alias.get(g, g)

        # graph inputs must all enter at stage 0 (they are injected there)
        input_guids = {p.guid for p in self.input_pts}
        for op in ops:
            for t in op.inputs:
                if resolve(t.guid) in input_guids and stage_of[op.guid] != 0:
                    warnings.warn(
                        "pipeline: a graph input is consumed past stage 0 "
                        "— running unpipelined"
                    )
                    return None

        batch = self.input_pts[0].material_shape()[0]
        consumers_stage: Dict[int, int] = {}
        for op in ops:
            for t in op.inputs:
                g = resolve(t.guid)
                consumers_stage[g] = max(
                    consumers_stage.get(g, -1), stage_of[op.guid]
                )
        cuts = []
        buf_elems = 0
        for s in range(len(stages) - 1):
            cut = []
            total = 0
            for op in ops:
                if stage_of[op.guid] > s:
                    continue
                for t in op.outputs:
                    if consumers_stage.get(t.guid, -1) <= s:
                        continue
                    shape = tuple(t.material_shape())
                    if not shape or shape[0] != batch:
                        warnings.warn(
                            "pipeline: a cut tensor is not batch-leading "
                            "— running unpipelined"
                        )
                        return None
                    if not np.issubdtype(t.data_type.np_dtype, np.floating):
                        warnings.warn(
                            "pipeline: non-float cut tensor — running "
                            "unpipelined"
                        )
                        return None
                    cut.append((t.guid, shape[1:], t.data_type.jnp_dtype))
                    n = 1
                    for d_ in shape[1:]:
                        n *= d_
                    total += n
            cuts.append(cut)
            buf_elems = max(buf_elems, total)
        out_pt = self.logits_pt
        return PcgPipelinePlan(
            stages=stages,
            cuts=cuts,
            buf_elems=buf_elems,
            out_guid=resolve(out_pt.guid),
            out_shape=tuple(out_pt.material_shape()),
            out_dtype=out_pt.data_type.jnp_dtype,
            n_stages=len(stages),
            alias=alias,
        )

    def _pipeline_stage_runners(self, training: bool, rng):
        """One runner per stage: executes that stage's ops exactly like
        apply()'s walk, minus sharding constraints (runners execute inside
        shard_map on device-local values)."""
        compute_index = {}
        idx = 0
        for op in self.topo:
            if not op.is_parallel_op:
                compute_index[op.guid] = idx
                idx += 1

        alias = getattr(self.pipeline_plan, "alias", {})

        def make_runner(sops):
            def run(params, vals, tick):
                consts = {}
                for guid, (pt, value) in self.constants.items():
                    if isinstance(value, np.ndarray):
                        consts[guid] = jnp.asarray(
                            value, pt.data_type.jnp_dtype
                        )
                    else:
                        consts[guid] = jnp.full(
                            pt.material_shape(), value,
                            pt.data_type.jnp_dtype,
                        )
                vals = dict(vals)
                for op in sops:
                    d = get_op_def(op.op_type)
                    ins = []
                    for t in op.inputs:
                        g = alias.get(t.guid, t.guid)
                        if g in vals:
                            ins.append(vals[g])
                        else:
                            ins.append(consts[g])
                    # fold the tick too: each micro-batch must draw its own
                    # dropout mask (one shared mask would correlate the
                    # micro-batches vs the unpipelined path)
                    op_rng = (
                        jax.random.fold_in(
                            jax.random.fold_in(rng, compute_index[op.guid]),
                            tick,
                        )
                        if rng is not None else None
                    )
                    ctx = FwdCtx(
                        training=training, rng=op_rng, seq_length=-1,
                        compute_dtype=self.compute_dtype, aux_losses=None,
                        n_devices=1, mesh=None,  # device-local inside shard_map
                        op_name=op.name,
                    )
                    with jax.named_scope(op.name):
                        outs = d.forward(
                            op.params, params.get(op.name, {}), ins, ctx
                        )
                    for t, v in zip(op.outputs, outs):
                        vals[t.guid] = v
                return vals
            return run

        return [make_runner(s) for s in self.pipeline_plan.stages]

    def _apply_pipelined(self, params, inputs: Dict[int, jax.Array], *,
                         training: bool, rng):
        """Forward through the generalized GPipe schedule; returns
        {logits_guid: value} (micro-batched stages; weights replicated
        over the pipe axis)."""
        from .pipeline import gpipe_pcg

        plan = self.pipeline_plan
        # input value order: resolve via a guid->value map; parallel ops
        # on inputs (degree bookkeeping) are identity device-local
        guids = [pt.guid for pt in self.input_pts]
        arrays = [inputs[g] for g in guids]
        out = gpipe_pcg(
            plan,
            self._pipeline_stage_runners(training, rng),
            params,
            arrays,
            guids,
            self.mesh,
        )
        return {plan.out_guid: out, self.logits_pt.guid: out}

    # -- parameter init (reference: initializer Legion tasks per weight) ----
    def init_params(self) -> Dict[str, Dict[str, jax.Array]]:
        key = jax.random.PRNGKey(self.seed)
        params: Dict[str, Dict[str, jax.Array]] = {}
        # local_devices: under multi-host, devices()[0] belongs to process
        # 0 — every other rank would compute init on a non-addressable
        # device. Same seed everywhere => identical draws on each host.
        with jax.default_device(jax.local_devices()[0]):
            for op in self.topo:
                if not op.weights:
                    continue
                wd: Dict[str, jax.Array] = {}
                for name, wpt in zip(op.weight_names, op.weights):
                    key, sub = jax.random.split(key)
                    init = get_initializer(op.initializers.get(name, "glorot_uniform"))
                    arr = init(sub, wpt.material_shape(), wpt.data_type.jnp_dtype)
                    sharding = sharding_for_parallel_tensor(wpt, self.mesh)
                    # via host numpy: under multi-host every process draws
                    # the SAME init (same seed) and contributes its local
                    # shards — a device-committed array cannot be reshard
                    # onto a mesh spanning other processes
                    if jax.process_count() > 1:
                        arr = np.asarray(arr)
                    wd[name] = jax.device_put(arr, sharding)
                params[op.name] = wd
        # PM_MERGE substitutions rebuild weights fresh from initializer
        # specs — running one after this point would discard trained
        # values (search/substitution_loader.py asserts on this flag)
        self.graph.weights_materialized = True
        return params

    def init_net_state(self) -> Dict[str, Dict[str, jax.Array]]:
        """Zero/one-filled cross-batch buffers for stateful ops (reference:
        cuDNN BN running stats init, Cache's first-batch fill)."""
        net: Dict[str, Dict[str, jax.Array]] = {}
        for op in self.topo:
            if op.is_parallel_op:
                continue
            d = get_op_def(op.op_type)
            if d.state_spec is None:
                continue
            specs = d.state_spec(
                op.params,
                [t.material_shape() for t in op.inputs],
                [t.data_type for t in op.inputs],
            )
            bufs = {}
            for spec in specs:
                fill = 1.0 if spec.initializer == "one" else 0.0
                arr = np.full(spec.shape, fill, spec.dtype.np_dtype)
                if self.mesh is not None:
                    bufs[spec.name] = jax.device_put(
                        arr, NamedSharding(self.mesh, PartitionSpec())
                    )
                else:
                    bufs[spec.name] = jnp.asarray(arr)
            net[op.name] = bufs
        return net

    def init_state(self) -> TrainState:
        params = self.init_params()
        opt_state = self.optimizer.init_state(params)
        # overlapped grad sync stores optimizer state sharded over the
        # data axis (ZeRO-1): the sharded update then never gathers it
        opt_state = self._place_opt_state_sharded(opt_state)
        return TrainState(params=params, opt_state=opt_state,
                          net_state=self.init_net_state())

    # -- forward ------------------------------------------------------------
    def _constrain(self, val, pt):
        spec = pspec_for_parallel_tensor(pt, self.mesh)
        if any(s is not None for s in spec):
            return jax.lax.with_sharding_constraint(
                val, NamedSharding(self.mesh, spec)
            )
        return val

    def apply(
        self,
        params,
        inputs: Dict[int, jax.Array],
        *,
        training: bool,
        rng: Optional[jax.Array],
        seq_length: int = -1,
        aux_out: Optional[list] = None,
        net_state: Optional[Dict] = None,
        net_out: Optional[Dict] = None,
    ) -> Dict[int, jax.Array]:
        """Walk the PCG and compute every tensor. Returns guid -> value.
        Differentiable aux losses (MoE balance) are appended to aux_out;
        stateful ops read net_state and write updates into net_out (the
        train step threads both; eval passes net_state read-only)."""
        if self.pipeline_plan is not None:
            if seq_length >= 0:
                raise NotImplementedError(
                    "per-iteration seq_length truncation changes the cut "
                    "tensor shapes and is not supported with the "
                    "generalized pipeline (pipeline_parallel_degree > 1 on "
                    "a non-block-stack graph)"
                )
            # generalized GPipe over the stage-partitioned graph; returns
            # only the output tensor (stage internals live per-device)
            return self._apply_pipelined(
                params, inputs, training=training, rng=rng
            )
        vals: Dict[int, jax.Array] = dict(inputs)
        for guid, (pt, value) in self.constants.items():
            if isinstance(value, np.ndarray):  # baked array constant
                vals[guid] = jnp.asarray(value, pt.data_type.jnp_dtype)
            else:
                vals[guid] = jnp.full(
                    pt.material_shape(), value, pt.data_type.jnp_dtype
                )
        aux = [aux_out]

        def run(op, vals, rng):
            # one scope per PCG operator, parallel operators included:
            # a device operation then names the graph node the search
            # priced, a collective the Repartition / Combine / Reduction
            # that caused it (trace time only)
            with jax.named_scope(op.name):
                ins = [vals[t.guid] for t in op.inputs]
                if op.is_parallel_op:
                    outs = par_ops.execute(op, ins, self.mesh)
                else:
                    opdef = get_op_def(op.op_type)
                    # fold in the op's index among COMPUTE ops, not its guid
                    # (process-global counter — a rebuilt model would draw
                    # different dropout masks for the same seed) and not its
                    # raw topo position (the search inserts partition/combine
                    # ops per mesh, which would make masks mesh-dependent)
                    op_rng = (
                        jax.random.fold_in(rng, self._compute_idx[op.guid])
                        if rng is not None else None
                    )
                    ctx = FwdCtx(
                        training=training,
                        rng=op_rng,
                        seq_length=seq_length,
                        compute_dtype=self.compute_dtype,
                        aux_losses=aux[0],
                        n_devices=self.mesh.size,
                        mesh=self.mesh,
                        op_name=op.name,
                    )
                    w = params.get(op.name, {})
                    if training and self.remat and op.op_type in _REMAT_OPS:
                        # Rematerialize in the backward instead of saving the
                        # op's internals — for attention that drops the stored
                        # s_q x s_kv scores/probs (the dominant HBM residual;
                        # measured 30x+ train-step speedup at seq 512 where the
                        # saved probs otherwise thrash HBM). Exact: same math,
                        # recomputed. RNG is closed over, so recompute is
                        # deterministic.
                        outs = jax.checkpoint(
                            lambda w_, ins_, _od=opdef, _p=op.params, _c=ctx: (
                                _od.forward(_p, w_, ins_, _c)
                            )
                        )(w, ins)
                    elif opdef.forward_stateful is not None:
                        st = (net_state or {}).get(op.name, {})
                        outs, new_st = opdef.forward_stateful(
                            op.params, w, st, ins, ctx
                        )
                        if net_out is not None:
                            # buffers are statistics, not a gradient path
                            net_out[op.name] = jax.tree_util.tree_map(
                                jax.lax.stop_gradient, new_st
                            )
                    else:
                        outs = opdef.forward(op.params, w, ins, ctx)
                for t, o in zip(op.outputs, outs):
                    vals[t.guid] = self._constrain(o, t)

        def run_loop(reg):
            """The region's body as ONE scan over its steps, the weights
            closed over (so a weight's gradient is the sum over the steps);
            an op's rng also folds in the step."""
            src = vals[reg.source.guid]
            outer, aux[0] = aux[0], None if aux_out is None else []
            had_aux = []

            def body(carry, u):
                bvals = dict(vals)
                bvals[reg.entry.outputs[0].guid] = self._constrain(
                    carry, reg.entry.outputs[0])
                if aux[0] is not None:
                    aux[0] = []
                for op in reg.body:
                    run(op, bvals,
                        None if rng is None else jax.random.fold_in(rng, u))
                losses = aux[0] or []
                had_aux.append(bool(losses))
                out = bvals[reg.exit.guid].astype(src.dtype)
                return out, sum(losses, jnp.zeros((), jnp.float32))

            with jax.named_scope("ff.loop"):
                out, step_aux = jax.lax.scan(
                    body, src, jnp.arange(reg.steps, dtype=jnp.int32))
            aux[0] = outer
            vals[reg.exit.guid] = out
            if outer is not None and any(had_aux):
                outer.append(jnp.sum(step_aux))

        for op in self.topo:
            reg = self._loop_of.get(op.guid)
            if reg is None:
                run(op, vals, rng)
            elif op is reg.ops[-1]:
                run_loop(reg)
        return vals

    # -- step functions -----------------------------------------------------
    def _input_vals(self, batch_arrays: List[jax.Array]) -> Dict[int, jax.Array]:
        assert len(batch_arrays) == len(self.input_pts), (
            f"model takes {len(self.input_pts)} inputs, got {len(batch_arrays)}"
        )
        return {pt.guid: a for pt, a in zip(self.input_pts, batch_arrays)}

    def _reg_penalty(self, params):
        """Weight-regularizer loss terms (reference applies L2 directly in
        the kernel-grad GEMM, linear_kernels.cu:333-350 grad += lambda*w;
        here the equivalent penalty lambda/2*||w||^2 joins the loss so
        jax.grad produces that same gradient)."""
        terms = []
        for op in self.topo:
            lam = getattr(op.params, "kernel_reg_lambda", 0.0)
            if not lam:
                continue
            w = params.get(op.name, {}).get("kernel")
            if w is None:
                continue
            mode = getattr(op.params, "kernel_reg_type", None)
            wf = w.astype(jnp.float32)
            if mode == RegularizerMode.REG_MODE_L1:
                terms.append(lam * jnp.sum(jnp.abs(wf)))
            else:
                terms.append(0.5 * lam * jnp.sum(wf * wf))
        return terms

    def mesh_is_live(self) -> bool:
        """Whether every device this executor's mesh spans is still in
        `jax.devices()`. False after a host loss / device shrink
        (runtime/elastic.py) — any further dispatch onto the stale mesh
        would hang or crash, so fit(elastic=True) recompiles the model
        for the surviving topology (FFModel.recompile_for_topology)
        before touching device state."""
        try:
            live = set(jax.devices())
        except Exception:
            return False
        return all(d in live for d in self.mesh.devices.flat)

    def note_step_duration(self, dur_s: float) -> None:
        """Feed the step-time EMA behind `drain_window_s`. fit() calls
        this only for SYNCED steps (health monitor / drain mode), where
        the wall time measured a whole step rather than an async
        dispatch."""
        if dur_s <= 0:
            return
        ema = getattr(self, "_step_dur_ema", None)
        self._step_dur_ema = (dur_s if ema is None
                              else 0.5 * ema + 0.5 * dur_s)

    @property
    def step_dur_ema(self) -> Optional[float]:
        """The measured synced-step wall-time EMA (None until fed). The
        StrategyTuner's drift watch and post-swap guard window read this
        (runtime/tuner.py)."""
        return getattr(self, "_step_dur_ema", None)

    def reset_step_duration(self) -> None:
        """Forget the step-time EMA. A strategy hot-swap installs a new
        executor whose steps must not be averaged against the pre-swap
        strategy's timings (runtime/tuner.py)."""
        self._step_dur_ema = None

    def drain_window_s(self, checkpoint_s: Optional[float] = None,
                       safety: float = 2.0) -> float:
        """How much of a preemption deadline must remain for fit() to
        risk ONE more step: the expected step time plus the expected
        checkpoint flush, with a safety factor (steps and flushes
        jitter; blowing the deadline means a hard kill mid-write, which
        costs a whole checkpoint interval of replay). The drain protocol
        keeps training while deadline_remaining() > this window, then
        flushes and leaves."""
        step = getattr(self, "_step_dur_ema", None) or 0.0
        ckpt = checkpoint_s or 0.0
        return safety * (step + ckpt) + 0.25

    def invalidate_step_cache(self, train_only: bool = False) -> None:
        """Drop cached jitted steps so the next build re-traces.

        Needed when a traced-as-constant hyperparameter changes (e.g. the
        learning rate from a keras LearningRateScheduler) — the Legion
        analogy is ending a captured trace when the task graph changes.
        `train_only` keeps the eval/forward traces, which don't see the
        optimizer's hyperparameters."""
        self._train_step = None
        self._train_step_nodonate = None
        self._train_scan = None
        self._grad_step = None
        for k in list(self._seq_len_cache):
            if k[0] == "grad" or not train_only:
                del self._seq_len_cache[k]
        if not train_only:
            self._eval_step = None
            self._fwd = None

    def _cast_grads(self, grads):
        """Half-width gradient storage (config.bf16_grads): cast every
        float grad leaf to grad_dtype. Integer/bool leaves (none today)
        and None pass through."""
        if self.grad_dtype is None:
            return grads
        return jax.tree_util.tree_map(
            lambda g: g.astype(self.grad_dtype)
            if jnp.issubdtype(g.dtype, jnp.floating) else g,
            grads,
        )

    def set_step_guard(self, cfg) -> None:
        """Enable/disable the NaN/Inf step guard (a
        resilience.StepGuardConfig or None). Invalidates the cached train
        step when the config actually changes — the guard is traced into
        the step program."""
        if cfg != self.step_guard:
            self.step_guard = cfg
            self._train_step = None
            self._train_step_nodonate = None
            self._train_scan = None

    def set_step_metrics(self, names) -> None:
        """Request extra per-step outputs in the metric partials
        (obs telemetry feed). Supported: ``"grad_norm"`` — the global
        gradient norm, already present whenever the step guard is armed,
        computed on demand otherwise. Traced into the step program, so a
        change invalidates the cached steps like set_step_guard."""
        names = tuple(names or ())
        unknown = [n for n in names if n != "grad_norm"]
        assert not unknown, f"unsupported step metrics: {unknown}"
        if names != self.step_metrics:
            self.step_metrics = names
            self._train_step = None
            self._train_step_nodonate = None
            self._train_scan = None

    # -- comm/compute-overlapped gradient sync ------------------------------
    def set_overlap_grad_sync(self, flag: bool) -> None:
        """Enable/disable the reduce-scatter + sharded-update + all-gather
        step decomposition. Traced into the step program, so a change
        invalidates the cached train steps (like set_step_guard)."""
        flag = bool(flag)
        if flag != self.overlap_grad_sync:
            self.overlap_grad_sync = flag
            self._overlap_spec_cache = None
            self._train_step = None
            self._train_step_nodonate = None
            self._train_scan = None

    def _overlap_specs(self) -> Dict:
        """(op name, weight name) -> (data-sharded, canonical) NamedSharding
        for every weight eligible for the overlapped update.

        The transform: constrain the weight's GRADIENT to a spec that
        additionally shards one replicated dim over the "data" axis — the
        XLA partitioner then lowers the pending cross-replica psum as a
        reduce-scatter instead of an all-reduce — run the (elementwise)
        optimizer update on the owned 1/d shard, and constrain the new
        param back to its canonical spec (an all-gather of UPDATED
        values). Wire bytes match the all-reduce exactly (RS + AG ==
        2(d-1)/d), but each weight's reduce-scatter depends only on that
        weight's gradient, so XLA's async-collective scheduler can
        overlap layer i's collective with layer i-1's backward matmuls —
        the reference's overlap_backward_update (config.h:133), with the
        optimizer state sharded ZeRO-1 style as a bonus (it never needs
        gathering; see init_state).

        Ineligible (left on the plain all-reduce path): weights already
        touching the data or fsdp axes (FSDP reduce-scatters on its own),
        and weights with no dim divisible by the data-axis size."""
        if self._overlap_spec_cache is not None:
            return self._overlap_spec_cache
        out: Dict = {}
        dsize = self.mesh.shape.get("data", 1) if self.mesh is not None else 1
        if not self.overlap_grad_sync or dsize <= 1:
            self._overlap_spec_cache = out
            return out
        for op in self.topo:
            for wname, wpt in zip(op.weight_names, op.weights):
                shape = tuple(wpt.material_shape())
                spec = list(pspec_for_parallel_tensor(wpt, self.mesh))
                spec += [None] * (len(shape) - len(spec))
                flat = set()
                for e in spec:
                    if isinstance(e, (tuple, list)):
                        flat.update(e)
                    elif e is not None:
                        flat.add(e)
                if "data" in flat or "fsdp" in flat:
                    continue
                for di, size in enumerate(shape):
                    if spec[di] is None and size >= dsize \
                            and size % dsize == 0:
                        sharded = list(spec)
                        sharded[di] = "data"
                        out[(op.name, wname)] = (
                            NamedSharding(self.mesh,
                                          PartitionSpec(*sharded)),
                            NamedSharding(self.mesh, PartitionSpec(*spec)),
                        )
                        break
        self._overlap_spec_cache = out
        return out

    def overlap_schedule(self):
        """Schedule-introspection hook for the static analyzer
        (analysis/schedule.py): the per-weight task chains this
        executor's overlapped step actually traces — backward →
        reduce-scatter(grad) → sharded update (donating opt state) →
        all-gather of updated params (donating the old param storage) —
        as an ``OverlapSchedule`` the FFA502 race detector can walk.
        Returns None when the overlapped path is off or inert (data
        degree 1 leaves ``_overlap_specs`` empty), matching the step
        the jit actually runs."""
        from ..analysis.schedule import build_overlap_schedule

        omap = self._overlap_specs()
        if not omap:
            return None
        return build_overlap_schedule(self.graph, set(omap.keys()))

    def _constrain_weight_tree(self, tree, omap, *, sharded: bool):
        """Apply the overlap shardings to a params-shaped
        {op: {weight: array}} tree (grads, params, or updated params)."""
        if not omap:
            return tree
        idx = 0 if sharded else 1
        return {
            op: {
                w: (jax.lax.with_sharding_constraint(v, omap[(op, w)][idx])
                    if (op, w) in omap and v is not None else v)
                for w, v in d.items()
            }
            for op, d in tree.items()
        }

    def _constrain_opt_state(self, tree, omap):
        """Constrain weight-shaped optimizer-state leaves to the sharded
        spec of the weight they mirror (identified by the leaf's trailing
        (op name, weight name) dict path — SGD's {"v": params-like},
        Adam's {"m"/"v": params-like}; scalars pass through)."""
        if not omap:
            return tree

        def f(path, leaf):
            if leaf is None or not hasattr(leaf, "shape"):
                return leaf
            keys = [p.key for p in path
                    if isinstance(p, jax.tree_util.DictKey)]
            if len(keys) >= 2 and (keys[-2], keys[-1]) in omap:
                return jax.lax.with_sharding_constraint(
                    leaf, omap[(keys[-2], keys[-1])][0]
                )
            return leaf

        return jax.tree_util.tree_map_with_path(
            f, tree, is_leaf=lambda x: x is None
        )

    def _place_opt_state_sharded(self, opt_state):
        """Host-side placement of fresh optimizer state on the overlap
        shardings: the sharded update reads and writes 1/d-sized state
        shards, so the state LIVES sharded across steps (ZeRO-1) — no
        all-gather of m/v ever happens, and opt-state HBM divides by the
        data degree. Checkpointing host-gathers shards transparently."""
        omap = self._overlap_specs()
        if not omap:
            return opt_state

        def f(path, leaf):
            if leaf is None or not hasattr(leaf, "shape"):
                return leaf
            keys = [p.key for p in path
                    if isinstance(p, jax.tree_util.DictKey)]
            if len(keys) >= 2 and (keys[-2], keys[-1]) in omap:
                return jax.device_put(leaf, omap[(keys[-2], keys[-1])][0])
            return leaf

        return jax.tree_util.tree_map_with_path(
            f, opt_state, is_leaf=lambda x: x is None
        )

    def init_guard_state(self) -> GuardState:
        assert self.step_guard is not None, "set_step_guard() first"
        cfg = self.step_guard
        return GuardState(
            loss_scale=jnp.asarray(cfg.init_loss_scale, jnp.float32),
            good_steps=jnp.asarray(0, jnp.int32),
            consecutive_skips=jnp.asarray(0, jnp.int32),
            total_skips=jnp.asarray(0, jnp.int32),
        )

    def _make_step(self, program: Optional[str] = "train_step"):
        """The train step's body. Its phases carry named scopes (trace
        time only: they change the operations' metadata, not the code):
        `ff.fwd` with one scope per PCG operator inside and `ff.loss`
        (the backward of each then reads `transpose(jvp(ff.fwd))/...`),
        `ff.grad_sync`, `ff.guard`, `ff.opt`, `ff.metrics`. `program`
        labels the trace counter (None: the caller counts its own)."""
        guard = self.step_guard
        # overlap shardings are trace-time constants of the step program
        omap = self._overlap_specs()

        def step(state: TrainState, batch_inputs, labels, rng, *extra):
            if program is not None:
                _count_trace(program)

            def loss_of(params):
                aux: list = []
                net_out: dict = {}
                with jax.named_scope("ff.fwd"):
                    vals = self.apply(
                        params, self._input_vals(batch_inputs), training=True,
                        rng=rng, aux_out=aux, net_state=state.net_state,
                        net_out=net_out,
                    )
                    logits = vals[self.logits_pt.guid]
                    with jax.named_scope("ff.loss"):
                        loss = self.loss_fn(logits, labels)
                        for a in aux:
                            loss = loss + a
                        for r in self._reg_penalty(params):
                            loss = loss + r
                if guard is not None:
                    # dynamic loss scaling: grads come out scaled and are
                    # unscaled below; the reported loss stays unscaled
                    return loss * state.guard.loss_scale, (loss, logits, net_out)
                return loss, (loss, logits, net_out)

            (_, (loss, logits, net_out)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(state.params)
            grads = self._cast_grads(grads)
            if omap:
                # overlapped grad sync: pin each eligible gradient to a
                # data-sharded layout, turning the pending cross-replica
                # psum into a per-weight reduce-scatter. Each weight's
                # collective depends only on that weight's gradient, so
                # the async-collective scheduler hides layer i's ICI
                # traffic behind layer i-1's backward matmuls. The guard
                # norm and the optimizer update below then run on the
                # owned 1/d shards (partial norms psum to one scalar —
                # no second full-tree traversal), and only the UPDATED
                # params all-gather back (see _overlap_specs).
                with jax.named_scope("ff.grad_sync"):
                    grads = self._constrain_weight_tree(grads, omap,
                                                        sharded=True)
            upd_src_params = (
                self._constrain_weight_tree(state.params, omap,
                                            sharded=True)
                if omap else state.params
            )
            new_net = dict(state.net_state)
            new_net.update(net_out)
            if guard is None:
                with jax.named_scope("ff.opt"):
                    new_params, new_opt = self.optimizer.update(
                        upd_src_params, grads, state.opt_state
                    )
                if omap:
                    # the all-gather of the updated parameters
                    with jax.named_scope("ff.grad_sync"):
                        new_params = self._constrain_weight_tree(
                            new_params, omap, sharded=False
                        )
                        new_opt = self._constrain_opt_state(new_opt, omap)
                new_guard = state.guard
                with jax.named_scope("ff.metrics"):
                    partials = self.metrics.compute(logits, labels)
                partials["loss"] = loss
                if "grad_norm" in self.step_metrics:
                    # telemetry feed (set_step_metrics): the guard path
                    # below always computes this; here it is opt-in
                    partials["grad_norm"] = global_grad_norm(grads)
            else:
                # -- NaN/Inf step guard (resilience.StepGuardConfig) ----
                # fit()'s fault-injection seam: extra[0] is a grad poison
                # multiplier (1.0 normally, NaN to simulate a bad batch)
                poison = extra[0] if extra else jnp.asarray(1.0, jnp.float32)
                inv = (poison / state.guard.loss_scale).astype(jnp.float32)
                with jax.named_scope("ff.guard"):
                    grads = jax.tree_util.tree_map(
                        lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype),
                        grads,
                    )
                    # under overlap the grads are data-sharded here, so
                    # this is a per-shard partial sum-of-squares + one
                    # scalar psum — the guard's old extra full-tree
                    # traversal is gone
                    gnorm = global_grad_norm(grads)
                    finite = jnp.isfinite(gnorm)
                with jax.named_scope("ff.opt"):
                    upd_params, upd_opt = self.optimizer.update(
                        upd_src_params, grads, state.opt_state
                    )
                # a skipped step carries params AND opt state through
                # unchanged — momentum/bias-correction must not advance
                # on a discarded gradient
                with jax.named_scope("ff.guard"):
                    new_params = _tree_select(finite, upd_params,
                                              upd_src_params)
                    new_opt = _tree_select(finite, upd_opt, state.opt_state)
                if omap:
                    with jax.named_scope("ff.grad_sync"):
                        new_params = self._constrain_weight_tree(
                            new_params, omap, sharded=False
                        )
                        new_opt = self._constrain_opt_state(new_opt, omap)
                g = state.guard
                cap = jnp.asarray(
                    guard.max_loss_scale
                    if guard.max_loss_scale is not None
                    else guard.init_loss_scale,
                    jnp.float32,
                )
                good = jnp.where(finite, g.good_steps + 1, 0)
                grow = finite & (good >= guard.growth_interval)
                backed = jnp.maximum(
                    g.loss_scale * guard.backoff_factor, guard.min_loss_scale
                )
                scale = jnp.where(
                    finite,
                    jnp.where(
                        grow,
                        jnp.minimum(g.loss_scale * guard.growth_factor, cap),
                        g.loss_scale,
                    ),
                    backed,
                )
                new_guard = GuardState(
                    loss_scale=scale,
                    good_steps=jnp.where(grow, 0, good).astype(jnp.int32),
                    consecutive_skips=jnp.where(
                        finite, 0, g.consecutive_skips + 1
                    ).astype(jnp.int32),
                    total_skips=(
                        g.total_skips + (1 - finite.astype(jnp.int32))
                    ),
                )
                # skipped steps contribute nothing to epoch metrics (their
                # logits/loss are NaN — summing would poison the epoch)
                with jax.named_scope("ff.metrics"):
                    partials = self.metrics.compute(logits, labels)
                partials["loss"] = loss
                partials = jax.tree_util.tree_map(
                    lambda v: jnp.where(finite, v, jnp.zeros_like(v)), partials
                )
                partials["skipped"] = 1.0 - finite.astype(jnp.float32)
                partials["grad_norm"] = jnp.where(finite, gnorm, 0.0)
            if self.mesh is not None:
                # pin metric partials replicated over the FULL mesh: under
                # multi-host, XLA may otherwise place these tiny outputs on
                # one process's devices, making them unfetchable elsewhere
                rep = NamedSharding(self.mesh, PartitionSpec())
                partials = {
                    k: jax.lax.with_sharding_constraint(v, rep)
                    for k, v in partials.items()
                }
                if guard is not None:
                    # guard counters are fetched per-step by fit's skip
                    # monitor — same multi-host placement concern
                    new_guard = jax.tree_util.tree_map(
                        lambda v: jax.lax.with_sharding_constraint(v, rep),
                        new_guard,
                    )
            return (
                TrainState(params=new_params, opt_state=new_opt,
                           step=state.step + 1, net_state=new_net,
                           guard=new_guard),
                partials,
            )

        return step

    def donates_buffers(self) -> bool:
        """One rule for every buffer a jitted step owns, the train step's
        state and the decode step's caches alike: donate on accelerators,
        where in-place buffer reuse halves peak weight/opt-state HBM and
        spares a decode step the copy of its whole KV cache — but NOT on
        CPU. On the CPU backend, an executable deserialized
        from the persistent compilation cache has been seen to lose the
        input/output aliasing metadata for donated buffers (the final
        state's buffers get reclaimed while still referenced, and live
        `model.state` arrays read back garbage once a later computation
        reuses the memory); not re-checked since, because CPU donation
        buys nothing — host RAM is not the scarce resource — so the safe
        choice costs nothing where it applies."""
        return jax.default_backend() != "cpu"

    def _donate_state(self) -> tuple:
        """donate_argnums for the train state (argument 0)."""
        return (0,) if self.donates_buffers() else ()

    def build_train_step(self, donate: bool = True) -> Callable:
        """donate=False builds a variant that never donates the input
        state, whatever the backend — required by the SDC/determinism
        canary (runtime/verify.py), which re-executes a step from the
        pre-step state: donation would have already reclaimed those
        buffers on accelerators."""
        if not donate:
            if self._train_step_nodonate is None:
                self._train_step_nodonate = jax.jit(self._make_step())
            return self._train_step_nodonate
        if self._train_step is None:
            self._train_step = jax.jit(self._make_step(),
                                       donate_argnums=self._donate_state())
        return self._train_step

    def time_train_step(self, state, batch_inputs, labels, rng, *,
                        repeats: int = 3, warmup: int = 1) -> float:
        """Wall-clock the REAL fused jitted training step (the step
        observatory's in-situ probe, obs/step_profile.py): mean seconds
        per step over `repeats` timed runs after `warmup` untimed ones.
        Uses the non-donating step variant so the caller's state (and
        the model's live params) survive the measurement untouched."""
        step = self.build_train_step(donate=False)
        parts = None
        for _ in range(max(1, warmup)):
            _, parts = step(state, batch_inputs, labels, rng)
            jax.block_until_ready(parts["loss"])  # fflint: disable=FFL103 — timing harness, the sync IS the measurement
        t0 = time.perf_counter()
        for _ in range(max(1, repeats)):
            _, parts = step(state, batch_inputs, labels, rng)
        jax.block_until_ready(parts["loss"])  # fflint: disable=FFL103 — timing harness, the sync IS the measurement
        return (time.perf_counter() - t0) / max(1, repeats)

    def build_train_scan(self) -> Callable:
        """Multi-step driver: lax.scan over pre-staged batches in ONE XLA
        program — the TPU-native analog of the reference's Legion trace
        replay around each training iteration (flexflow_cffi.py:2093-2102
        begin_trace/end_trace), amortizing per-step host dispatch. Takes
        (state, stacked_inputs, stacked_labels, rngs) where every batch
        array AND the rng keys carry a leading steps axis — the caller
        supplies one key per step, so stochastic ops (dropout) see the
        exact same streams as the one-dispatch-per-step path. Returns the
        final state and per-step-stacked metric partials."""
        if self._train_scan is not None:
            return self._train_scan
        assert self.step_guard is None, (
            "the fused multi-step scan driver does not take the step "
            "guard's per-step poison/skip monitoring; resilient fit() "
            "dispatches stepwise (build_train_step)"
        )
        step = self._make_step(program=None)

        def multi(state, stacked_inputs, stacked_labels, rngs):
            _count_trace("train_scan")

            def body(st, xs):
                ins, lab, key = xs
                st2, partials = step(st, ins, lab, key)
                return st2, partials

            state, partials = jax.lax.scan(
                body, state, (list(stacked_inputs), stacked_labels, rngs)
            )
            return state, partials

        self._train_scan = jax.jit(multi,
                                   donate_argnums=self._donate_state())
        return self._train_scan

    def build_grad_step(self, seq_length: int = -1) -> Callable:
        """Gradient-only step for the cffi-parity stepwise loop
        (FFModel.backward). Uses the SAME loss as the fused train step —
        including MoE aux losses and regularizer penalties — so stepwise
        training matches fit() exactly."""
        if seq_length < 0 and self._grad_step is not None:
            return self._grad_step
        if seq_length >= 0 and ("grad", seq_length) in self._seq_len_cache:
            return self._seq_len_cache[("grad", seq_length)]

        def grad_of(params, batch_inputs, labels, net_state=None):
            def loss_of(p):
                aux: list = []
                net_out: dict = {}
                vals = self.apply(
                    p, self._input_vals(batch_inputs), training=True,
                    rng=None, aux_out=aux, seq_length=seq_length,
                    net_state=net_state, net_out=net_out,
                )
                logits = vals[self.logits_pt.guid]
                loss = self.loss_fn(logits, truncate_labels(labels, logits))
                for a in aux:
                    loss = loss + a
                for r in self._reg_penalty(p):
                    loss = loss + r
                return loss, net_out

            grads, net_out = jax.grad(loss_of, has_aux=True)(params)
            return self._cast_grads(grads), net_out

        fn = jax.jit(grad_of)
        if seq_length < 0:
            self._grad_step = fn
        else:
            self._seq_len_cache[("grad", seq_length)] = fn
        return fn

    def build_eval_step(self) -> Callable:
        if self._eval_step is not None:
            return self._eval_step

        def step(params, batch_inputs, labels, net_state=None):
            vals = self.apply(
                params, self._input_vals(batch_inputs), training=False,
                rng=None, net_state=net_state,
            )
            logits = vals[self.logits_pt.guid]
            partials = self.metrics.compute(logits, labels)
            partials["loss"] = self.loss_fn(logits, labels)
            return logits, partials

        self._eval_step = jax.jit(step)
        return self._eval_step

    def build_forward(self, seq_length: int = -1) -> Callable:
        """seq_length >= 0 truncates seq-aware ops per iteration (reference:
        FFIterationConfig.seq_length, forward(seq_length) model.h:771 —
        BatchMatmul a/b_seq_length_dim slicing). Each distinct value is its
        own compiled executable, like the reference re-runs its tasks with
        the iteration config."""
        if seq_length < 0:
            if self._fwd is not None:
                return self._fwd
        elif ("fwd", seq_length) in self._seq_len_cache:
            return self._seq_len_cache[("fwd", seq_length)]

        def fwd(params, batch_inputs, net_state=None):
            vals = self.apply(
                params, self._input_vals(batch_inputs), training=False,
                rng=None, seq_length=seq_length, net_state=net_state,
            )
            return vals[self.logits_pt.guid]

        fn = jax.jit(fwd)
        if seq_length < 0:
            self._fwd = fn
        else:
            self._seq_len_cache[("fwd", seq_length)] = fn
        return fn

    # -- incremental decode (serving KV cache) ------------------------------
    def build_decode(self, batch: int, max_len: int, cache_dtype=None,
                     decode_input: Optional[int] = None,
                     assume_causal: bool = False):
        """(init_caches, step) for KV-cache autoregressive decoding over an
        arbitrary causal decoder or encoder-decoder PCG, built by
        parallel/decode.py (its liveness/prefix analysis and build_step —
        graphs imported from HF build attention from primitive
        batch_matmul/softmax/mask ops and still decode O(1)/token) and
        memoised here by its arguments.

        init_caches(params=None, static_inputs=()) computes the static
        (encoder-side) subgraph once and zero-fills the prefix/KV caches;
        decoder-only graphs keep the old zero-arg call. step(params,
        caches, t, [token_block]) runs the newest positions: seq-pointwise
        ops execute on the (batch, s0, ...) slice, attention appends this
        block's K/V and attends against the prefix, cross-attention
        attends the precomputed encoder K/V, and static/constant operands
        (positional tables, masks) are sliced per step.

        An op that keeps a state of fixed size in place of keys and values
        (the gated delta-rule mixer, ops/linear_attention.py) has a cache
        section of its own, caches["recurrent"][op.name] = (S, conv_tail),
        zero at init_caches. Such a state is never overwritten position by
        position, so a block that is padded (serving's prefill buckets)
        says how many of its tokens are real: step(..., valid), a scalar or
        a (batch,) count; positions at or beyond it leave the state as it
        was. None means the whole block. A caller that wants one row of a
        block's output (a prefill wants the last real token's logits, not
        bucket x vocabulary of them) says which: step(..., row), a scalar
        or a (batch,) index into the block; what follows the last op that
        mixes positions then runs on that row alone and the output has one
        position.

        step's `t` may be a scalar (the generate APIs: every row at the
        same position) or a (batch,) int vector of per-row positions —
        the continuous-batching contract (runtime/serving.py): each slot
        of a running decode batch advances through its own sequence, so
        K/V appends and causality masks are applied per row.

        step CONSUMES `caches`: on an accelerator the argument is donated
        (donates_buffers, the rule of the train step's state), so XLA
        appends to the cache leaves in place instead of copying every one
        of them first, and the arrays handed in are deleted. The caller rebinds
        from the return value, `logits, caches = step(params, caches, ...)`,
        and reads nothing of the old tree afterwards (tools/fflint.py
        FFL102). init_caches hands out buffers that belong to the caches
        alone, so a step consumes nothing of the caller's.

        Build-time validation rejects graphs the scheme can't prove exact:
        ops mixing sequence positions without a decode rule, non-causal
        self-attention, softmax over the live axis."""
        from . import decode as dec

        donate = self.donates_buffers()
        key = (batch, max_len, cache_dtype, decode_input, assume_causal,
               donate)
        built = self._decode_builds.get(key)
        if built is None:
            built = self._decode_builds[key] = dec.build_step(
                self.topo, self.input_pts, self.constants, self.logits_pt,
                self.compute_dtype, batch=batch, max_len=max_len,
                cache_dtype=cache_dtype, decode_input=decode_input,
                assume_causal=assume_causal, donate=donate,
                loops=self.loops)
        return built

    # -- data placement -----------------------------------------------------
    def shard_batch(self, pt, array) -> jax.Array:
        sharding = sharding_for_parallel_tensor(pt, self.mesh)
        return jax.device_put(array, sharding)

    def shard_batch_stack(self, pt, array) -> jax.Array:
        """Place a (steps, *batch_shape) stack for build_train_scan: the
        leading steps axis is unsharded, per-step dims shard as usual."""
        spec = pspec_for_parallel_tensor(pt, self.mesh)
        return jax.device_put(
            array, NamedSharding(self.mesh, PartitionSpec(None, *spec))
        )

    def put_replicated(self, array) -> jax.Array:
        """Place host data replicated over the FULL mesh. Required under
        multi-host (runtime/distributed.py): a plain jnp.asarray commits to
        one local device, and jit cannot reshard a single-device-committed
        array onto a mesh spanning other processes — labels and rng keys
        must enter as global arrays."""
        if self.mesh is None:
            return jnp.asarray(array)
        return jax.device_put(array, NamedSharding(self.mesh, PartitionSpec()))
