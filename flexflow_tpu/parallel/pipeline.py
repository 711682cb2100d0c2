"""GPipe-style SPMD pipeline parallelism over a mesh axis.

The reference DECLARES pipeline parallelism but never implements it:
`OP_PIPELINE` exists only as an enum (ffconst.h:158) and task IDs
(model.h:190-192) with no source file (SURVEY §2.3). This module supplies
the capability TPU-natively, the way XLA wants it expressed: every device
runs the SAME program (SPMD), stage placement is a sharding of the stacked
layer weights over a "pipe" mesh axis, and activations move between stages
with `lax.ppermute` hops over the ICI ring.

Schedule: GPipe. The local batch is split into `n_micro` microbatches; for
`n_micro + n_stages - 1` ticks, each device (stage) computes its layer
group on the activation it holds, then the ring rotates activations one hop
so stage s+1 sees stage s's output next tick. Stage 0 injects a fresh
microbatch each of the first `n_micro` ticks; the last stage collects
finished microbatches. The whole schedule is a `lax.scan`, so jax.grad
differentiates it — backward is automatically the reverse pipeline
(ppermute transposes to the opposite rotation).

Bubble fraction is (n_stages-1)/(n_micro+n_stages-1), the GPipe figure;
raise num_microbatches to amortize.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def scan_blocks(block_fn: Callable, stacked_params, x):
    """Degenerate (single-stage) path: run all stacked layers sequentially.
    `stacked_params` leaves have a leading num_layers dim."""

    def body(h, layer_w):
        return block_fn(layer_w, h), None

    out, _ = lax.scan(body, x, stacked_params)
    return out


def _stage_apply(block_fn: Callable, local_params, h):
    """Apply this stage's layer group (leaves have leading layers/stage dim)."""

    def body(c, layer_w):
        return block_fn(layer_w, c), None

    out, _ = lax.scan(body, h, local_params)
    return out


def gpipe_spmd(
    block_fn: Callable,
    stacked_params,
    x,
    *,
    n_stages: int,
    n_micro: int,
    mesh,
    axis_name: str = "pipe",
    data_axis: str = "data",
):
    """Run `n_stages * layers_per_stage` stacked blocks as a GPipe pipeline.

    stacked_params: pytree whose leaves have leading dim num_layers,
    sharded over `axis_name`. x: (batch, ...) activation, sharded over
    `data_axis` on dim 0. Returns the same-shaped output, replicated over
    the pipe axis (every stage ends up with the full result via psum of a
    buffer that is zero off the last stage).
    """
    num_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert num_layers % n_stages == 0, (
        f"{num_layers} layers not divisible into {n_stages} stages"
    )
    dp = mesh.shape.get(data_axis, 1)
    b_local = x.shape[0] // dp
    # clamp the schedule to what the local batch can supply: the largest
    # divisor of b_local not exceeding the requested microbatch count
    n_micro = max(1, min(n_micro, b_local))
    while b_local % n_micro:
        n_micro -= 1

    def pipelined(local_params, x_local):
        stage = lax.axis_index(axis_name)
        mb = x_local.shape[0] // n_micro
        mbs = x_local.reshape((n_micro, mb) + x_local.shape[1:])
        ticks = n_micro + n_stages - 1
        # carries become pipe-varying inside the loop (ppermute / stage
        # predicates), so the initial zeros must carry that vma type too
        zero_x = lax.pcast(jnp.zeros_like(mbs[0]), (axis_name,), to="varying")
        zero_out = lax.pcast(jnp.zeros_like(mbs), (axis_name,), to="varying")
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def tick(carry, t):
            x_cur, outbuf = carry
            inj = lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            x_in = jnp.where(stage == 0, inj, x_cur)
            y = _stage_apply(block_fn, local_params, x_in)
            out_idx = t - (n_stages - 1)
            oi = jnp.clip(out_idx, 0, n_micro - 1)
            old = lax.dynamic_index_in_dim(outbuf, oi, 0, keepdims=False)
            valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outbuf = lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(valid, y, old), oi, 0
            )
            x_next = lax.ppermute(y, axis_name, perm)
            return (x_next, outbuf), None

        (_, outbuf), _ = lax.scan(tick, (zero_x, zero_out), jnp.arange(ticks))
        # off-last-stage buffers are all zeros -> psum replicates the result
        out = lax.psum(outbuf, axis_name)
        return out.reshape(x_local.shape)

    param_specs = jax.tree_util.tree_map(
        lambda l: P(*((axis_name,) + (None,) * (l.ndim - 1))), stacked_params
    )
    x_spec = P(*((data_axis,) + (None,) * (x.ndim - 1)))
    fn = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
    )
    return fn(stacked_params, x)


# ---------------------------------------------------------------------------
# Generalized pipeline over an ARBITRARY PCG (non-uniform models, CNNs)
# ---------------------------------------------------------------------------
# The block-stack path above needs identical layers (stage placement = a
# sharding of stacked weights). For an arbitrary op chain the stages are
# heterogeneous: different subgraphs, different activation shapes. Under
# SPMD that becomes: every device runs `lax.switch` over its stage index
# (each branch = one stage's subgraph), and inter-stage activations travel
# in a FIXED-SIZE flat f32 buffer (padded to the widest cut) so ppermute
# has one uniform carrier type. Weights stay replicated over the pipe axis
# — this trades the block-stack path's weight-memory sharding for
# generality (compute still pipelines; the reference has neither:
# OP_PIPELINE is enum-only, ffconst.h:158).

import dataclasses
from typing import Any, List, Tuple


@dataclasses.dataclass
class PcgPipelinePlan:
    """Stage partition of a PCG's compute ops (contiguous in topo order)."""

    stages: List[List]  # per stage: PCGOps
    # per cut s (between stage s and s+1): [(guid, shape_wo_batch, dtype)]
    cuts: List[List[Tuple[int, Tuple[int, ...], Any]]]
    buf_elems: int  # flat f32 elems per sample, max over cuts + output
    out_guid: int
    out_shape: Tuple[int, ...]  # global shape
    out_dtype: Any
    n_stages: int
    # parallel-op output guid -> producing compute tensor guid (identity
    # bookkeeping resolved at plan time)
    alias: dict = dataclasses.field(default_factory=dict)


def balanced_linear_partition(costs: List[float], k: int) -> List[int]:
    """Contiguous partition of `costs` into k groups minimizing the max
    group sum (classic linear-partition DP) — this is how "the search
    proposes the cut": op costs come from the analytic cost model.
    Returns cut indices: group j = ops[cut[j]:cut[j+1]]."""
    n = len(costs)
    k = min(k, n)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def group(a, b):
        return prefix[b] - prefix[a]

    INF = float("inf")
    dp = [[INF] * (k + 1) for _ in range(n + 1)]
    cut = [[0] * (k + 1) for _ in range(n + 1)]
    dp[0][0] = 0.0
    for i in range(1, n + 1):
        for j in range(1, min(i, k) + 1):
            for m in range(j - 1, i):
                v = max(dp[m][j - 1], group(m, i))
                if v < dp[i][j]:
                    dp[i][j] = v
                    cut[i][j] = m
    bounds = [n]
    i, j = n, k
    while j > 0:
        i = cut[i][j]
        bounds.append(i)
        j -= 1
    return list(reversed(bounds))


def gpipe_pcg(
    plan: PcgPipelinePlan,
    stage_runners: List,  # stage s: fn(params, vals_dict) -> vals_dict
    params,
    input_arrays: List,  # global graph inputs, batch-leading
    input_guids: List[int],
    mesh,
    *,
    n_micro: int = 0,
    axis_name: str = "pipe",
    data_axis: str = "data",
):
    """Run the planned stages as a GPipe schedule. Inputs are injected at
    stage 0 (ints allowed — they bypass the f32 cut buffer); the final
    output returns replicated over the pipe axis."""
    n_stages = plan.n_stages
    dp = mesh.shape.get(data_axis, 1)
    batch = input_arrays[0].shape[0]
    b_local = batch // dp
    n_micro = n_micro or n_stages
    n_micro = max(1, min(n_micro, b_local))
    while b_local % n_micro:
        n_micro -= 1
    out_flat = 1
    for s in plan.out_shape[1:]:
        out_flat *= s
    buf_elems = max(plan.buf_elems, out_flat)

    def unpack(buf, cut, mb):
        vals = {}
        off = 0
        for guid, shp, dt in cut:
            size = 1
            for s in shp:
                size *= s
            vals[guid] = buf[:, off:off + size].reshape((mb,) + shp).astype(dt)
            off += size
        return vals

    def pack(vals, cut, mb):
        parts = [
            vals[guid].astype(jnp.float32).reshape(mb, -1)
            for guid, _, _ in cut
        ]
        flat = (jnp.concatenate(parts, axis=1) if parts
                else jnp.zeros((mb, 0), jnp.float32))
        pad = buf_elems - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat

    def pipelined(params, *inputs_local):
        # Make the replicated params VARYING up front: consumed as-is
        # inside the scan they'd each get an implicit pvary whose
        # transpose is a per-tick psum INSIDE the backward While loop,
        # racing the reverse ppermute across devices (observed XLA:CPU
        # rendezvous deadlock: half the mesh at an allreduce, half at a
        # permute). One explicit pvary here moves the whole param-grad
        # psum after the scan, where it is data-dependent on every
        # ppermute and cannot race.
        axes = (data_axis, axis_name)
        params = jax.tree_util.tree_map(
            lambda l: lax.pcast(l, axes, to="varying"), params
        )
        stage = lax.axis_index(axis_name)
        mb = inputs_local[0].shape[0] // n_micro
        mbs = [a.reshape((n_micro, mb) + a.shape[1:]) for a in inputs_local]
        ticks = n_micro + n_stages - 1
        # carriers are varying over BOTH the pipe axis (ppermute/stage
        # predicates) and the data axis (they mix with data-sharded
        # activations inside the branches)
        zero_buf = lax.pcast(
            jnp.zeros((mb, buf_elems), jnp.float32),
            (data_axis, axis_name), to="varying",
        )
        zero_out = lax.pcast(
            jnp.zeros((n_micro, mb, out_flat), jnp.float32),
            (data_axis, axis_name), to="varying",
        )
        perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def make_branch(s):
            def branch(buf, inj, t):
                if s == 0:
                    vals = dict(zip(input_guids, inj))
                else:
                    vals = unpack(buf, plan.cuts[s - 1], mb)
                vals = stage_runners[s](params, vals, t)
                if s == n_stages - 1:
                    out = vals[plan.out_guid].astype(jnp.float32)
                    flat = out.reshape(mb, -1)
                    pad = buf_elems - flat.shape[1]
                    return jnp.pad(flat, ((0, 0), (0, pad)))
                return pack(vals, plan.cuts[s], mb)
            return branch

        branches = [make_branch(s) for s in range(n_stages)]

        def tick(carry, t):
            buf, outbuf = carry
            # injected inputs must carry the pipe-varying vma type so every
            # switch branch (buf-derived or inj-derived) has one output type
            inj = [
                lax.pcast(
                    lax.dynamic_index_in_dim(
                        m, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
                    ),
                    (axis_name,), to="varying",
                )
                for m in mbs
            ]
            y = lax.switch(stage, branches, buf, inj, t)
            out_idx = t - (n_stages - 1)
            oi = jnp.clip(out_idx, 0, n_micro - 1)
            old = lax.dynamic_index_in_dim(outbuf, oi, 0, keepdims=False)
            valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outbuf = lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(valid, y[:, :out_flat], old), oi, 0
            )
            buf_next = lax.ppermute(y, axis_name, perm)
            return (buf_next, outbuf), None

        # unrolled: the tick count is small (n_micro + n_stages - 1) and
        # XLA:CPU's thunk executor races independent collectives across
        # devices when they sit inside a While body (observed deadlock:
        # half the mesh at the param-grad allreduce, half at a ppermute);
        # a flat thunk graph gives every device one static order
        (_, outbuf), _ = lax.scan(tick, (zero_buf, zero_out),
                                  jnp.arange(ticks), unroll=True)
        out = lax.psum(outbuf, axis_name)
        local_shape = (b_local,) + tuple(plan.out_shape[1:])
        return out.reshape(local_shape).astype(plan.out_dtype)

    in_specs = tuple(
        P(*((data_axis,) + (None,) * (a.ndim - 1))) for a in input_arrays
    )
    param_specs = jax.tree_util.tree_map(lambda _: P(), params)
    out_spec = P(*((data_axis,) + (None,) * (len(plan.out_shape) - 1)))
    fn = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(param_specs,) + in_specs,
        out_specs=out_spec,
    )
    return fn(params, *input_arrays)
