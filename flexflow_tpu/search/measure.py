"""On-device operator microbenchmarks for the measured-mode cost model.

The reference's Simulator measures every operator's fwd/bwd on the GPU and
caches by (op-params, machine-view) hash (simulator.cc:489-537,
Op::measure_operator_cost per op, inner_measure_operator_cost
operator.h:127 — cudaEvent timing with warmup + repeats). This module is
the TPU equivalent: jit the op's forward (and its VJP) at the view's
per-shard shapes, run R repetitions inside ONE lax.scan dispatch (a
microsecond op is shorter than one host dispatch, so per-call host timing
would measure the launch), and feed the
(fwd, bwd) seconds into CostModel.measured so the Unity search steers by
real silicon instead of the analytic roofline.

Enable with FFConfig.measure_operator_costs (argv: --measured-search).
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, Optional, Tuple

import numpy as np

from ..ff_types import DataType
from ..ops.registry import FwdCtx, get_op_def


def _local_shape(pt) -> Tuple[int, ...]:
    """The per-shard material shape under the tensor's sharding degrees."""
    return tuple(
        d.size // max(1, d.degree)
        for d in pt.dims
        if not d.is_replica_dim
    )


def _dummy(shape, data_type: DataType, rng: np.random.RandomState):
    import jax.numpy as jnp

    dt = data_type.jnp_dtype
    if data_type in (DataType.DT_INT32, DataType.DT_INT64):
        return jnp.asarray(rng.randint(0, 2, shape), dt)
    return jnp.asarray(rng.rand(*shape).astype(np.float32), dt)


def _chain_first_float(ws: Dict, ins: list, feedback):
    """Tie one float operand to the scan carry NONLINEARLY so XLA cannot
    hoist the measured op out of the repetition loop. A perturbation
    linear in the carry is not enough: dot distributes over addition, so
    (x + c*eps) @ W rewrites to the loop-invariant x@W plus a hoisted
    rank-1 correction, and the 'measurement' collapses to a scale-add
    (observed on TPU: a 4096x1024x1024 gemm timed at a physically
    impossible 2879 TF/s). sin(c + iota) is elementwise-nonlinear in c,
    so even a distributing rewrite must run a same-shape matmul every
    iteration. The 1e-30 scale keeps it numerically inert."""
    import jax
    import jax.numpy as jnp

    def tie(a):
        mix = jax.lax.broadcasted_iota(
            jnp.float32, a.shape, max(0, a.ndim - 1)
        )
        d = jnp.sin(feedback.astype(jnp.float32) + mix) * 1e-30
        return (a.astype(jnp.float32) + d).astype(a.dtype)

    for i, a in enumerate(ins):
        if jnp.issubdtype(a.dtype, jnp.floating):
            ins = list(ins)
            ins[i] = tie(a)
            return ws, ins
    for k in ws:
        if jnp.issubdtype(ws[k].dtype, jnp.floating):
            ws = dict(ws)
            ws[k] = tie(ws[k])
            return ws, ins
    return ws, ins


class OperatorMeasurer:
    """Times op fwd/bwd on the current default jax device.

    Cached by (op_type, params, local input/weight shapes) — the view
    enters only through the shard shapes, like the reference's strict
    hash (simulator.cc strict_hash_to_operator_cost)."""

    def __init__(self, *, repeats: int = 50, warmup: int = 1,
                 compute_dtype=None, differenced: Optional[bool] = None,
                 cache_path: Optional[str] = None):
        self.repeats = repeats
        self.warmup = warmup
        self.compute_dtype = compute_dtype
        # R-vs-4R differencing cancels what every timed call pays once
        # whatever R is — the host dispatch, the launch of the scan and
        # the device->host fetch of its scalar — but costs extra compiles
        # per op. On the CPU (tests) that constant is small beside the
        # scan itself: time one scan directly — same cache semantics,
        # ~6x fewer XLA compiles.
        # None = decide from the backend at first measurement (deciding
        # here would force jax backend init at construction time).
        self._differenced = differenced
        self._cache: Dict[Tuple, Tuple[float, float]] = {}
        self._warned: set = set()
        # disk persistence (reference: the Simulator caches its on-device
        # microbenchmarks across runs, simulator.cc:489-537): measurements
        # survive process restarts, so repeated --measured-search compiles
        # pay the silicon cost once per (op, shard-shape)
        self.cache_path = cache_path
        self._disk: Dict[str, Tuple[float, float]] = {}
        self._disk_loaded = False

    def _cache_meta(self) -> Dict[str, str]:
        import jax

        return {
            "device": jax.devices()[0].device_kind,
            "dtype": str(self.compute_dtype or "f32"),
        }

    def _load_disk(self) -> None:
        """Lazy (first measurement): the cache is only valid for the SAME
        device kind and compute dtype — timings from another chip replayed
        silently would poison every downstream cost."""
        self._disk_loaded = True
        if not self.cache_path:
            return
        import json
        import os

        if not os.path.exists(self.cache_path):
            return
        try:
            with open(self.cache_path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            warnings.warn(
                f"measured-search: ignoring unreadable cache "
                f"{self.cache_path}: {e}"
            )
            return
        meta = data.pop("__meta__", None)
        if meta is not None and meta != self._cache_meta():
            warnings.warn(
                f"measured-search: cache {self.cache_path} was measured on "
                f"{meta} but this run is {self._cache_meta()} — ignoring it"
            )
            return
        if meta is None:
            warnings.warn(
                f"measured-search: cache {self.cache_path} has no device "
                "metadata (older format); assuming it matches this device"
            )
        self._disk = {k: tuple(v) for k, v in data.items()}

    @staticmethod
    def _disk_key(key) -> str:
        op_type, params, shard_shapes, w_shapes, parts = key
        return f"{op_type.name}|{params!r}|{shard_shapes}|{w_shapes}|{parts}"

    def _disk_put(self, key, fb) -> None:
        if not self.cache_path:
            return
        import json

        self._disk[self._disk_key(key)] = fb
        try:
            payload = {"__meta__": self._cache_meta()}
            payload.update({k: list(v) for k, v in self._disk.items()})
            with open(self.cache_path, "w") as f:
                json.dump(payload, f, indent=0)
        except OSError as e:
            warnings.warn(f"measured-search: cache write failed: {e}")

    @property
    def differenced(self) -> bool:
        if self._differenced is None:
            import jax

            self._differenced = jax.default_backend() == "tpu"
        return self._differenced

    def __call__(self, op, view, *, force: bool = False) -> Tuple[float, float]:
        """force=True bypasses the cache READ (a fresh measurement still
        lands in the cache) — used when re-measuring outliers at higher
        repeat counts."""
        parts = max(1, view.num_parts())
        shard_shapes = tuple(_local_shape(t) for t in op.inputs)
        w_shapes = tuple(_local_shape(w) for w in op.weights)
        key = (op.op_type, op.params, shard_shapes, w_shapes, parts)
        if not self._disk_loaded:
            self._load_disk()
        if not force:
            if key in self._cache:
                return self._cache[key]
            disk = self._disk.get(self._disk_key(key))
            if disk is not None:
                self._cache[key] = disk
                return disk
        try:
            fb = self._measure(op, shard_shapes, w_shapes)
        except Exception as e:
            # un-runnable standalone (e.g. params that disagree with local
            # weight shards): analytic fallback — but say so ONCE per op
            # type, or measured mode silently degrades to the roofline
            if op.op_type not in self._warned:
                self._warned.add(op.op_type)
                warnings.warn(
                    f"measured-search: {op.op_type.name} fell back to the "
                    f"analytic cost model ({type(e).__name__}: {e})"
                )
            fb = None
        if fb is None:
            fb = (float("nan"), float("nan"))
        else:
            self._disk_put(key, fb)
        self._cache[key] = fb
        return fb

    def _measure(self, op, shard_shapes, w_shapes):
        import jax
        import jax.numpy as jnp

        if op.is_parallel_op or not op.inputs:
            return None
        opdef = get_op_def(op.op_type)
        rng = np.random.RandomState(0)
        inputs = [
            _dummy(s, t.data_type, rng)
            for s, t in zip(shard_shapes, op.inputs)
        ]
        # weight names from the WeightSpecs (so dict lookups in the
        # forward resolve), shapes from the op's ParallelTensors at their
        # PER-SHARD sizes — a channel-split kernel must be timed at
        # out_channels/degree, not full size
        specs = opdef.weights(
            op.params,
            [tuple(s) for s in shard_shapes],
            [t.data_type for t in op.inputs],
        ) if opdef.weights else []
        weights = {
            spec.name: _dummy(ws, w.data_type, rng)
            for spec, ws, w in zip(specs, w_shapes, op.weights)
        }
        ctx = FwdCtx(training=False, rng=None, seq_length=-1,
                     compute_dtype=self.compute_dtype, aux_losses=None,
                     n_devices=1, mesh=None)
        R = self.repeats

        def fwd_once(ws, ins):
            outs = opdef.forward(op.params, ws, ins, ctx)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

        diffable = [i for i, a in enumerate(inputs)
                    if jnp.issubdtype(a.dtype, jnp.floating)]

        def fwd_body(c, _):
            ws2, ins2 = _chain_first_float(weights, inputs, c)
            return c + fwd_once(ws2, ins2) * 1e-9, ()

        def bwd_body(c, _):
            def loss(ws_, dins):
                full = list(inputs)
                for i, v in zip(diffable, dins):
                    full[i] = v
                return fwd_once(ws_, full)

            ws2, ins2 = _chain_first_float(weights, inputs, c)
            g = jax.grad(loss, argnums=(0, 1))(
                ws2, [ins2[i] for i in diffable]
            )
            leaves = jax.tree_util.tree_leaves(g)
            return c + sum(
                jnp.sum(l.astype(jnp.float32)) for l in leaves
            ) * 1e-9, ()

        def run(body, length):
            fn = jax.jit(lambda: jax.lax.scan(
                body, jnp.float32(0.0), None, length=length)[0])
            for _ in range(self.warmup):
                float(fn())
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                float(fn())
                best = min(best, time.perf_counter() - t0)
            return best

        def per_rep_seconds(body):
            """Time scans of R and 4R reps and difference them: the fixed
            dispatch + launch + device->host fetch cancels, leaving pure
            per-repetition op time (the reference's cudaEvent bracket
            equivalent). R grows until the differenced signal clears
            20 ms, well above host timing jitter, and each point is a
            min-of-3. Non-differenced mode (the CPU backend) times one
            scan directly."""
            if not self.differenced:
                return max(run(body, R) / R, 1e-9)
            reps = R
            while True:
                t1 = run(body, reps)
                t4 = run(body, 4 * reps)
                signal = t4 - t1
                if signal > 20e-3 or reps >= 4096:
                    return max(signal / (3 * reps), 1e-9)
                reps *= 4

        fwd_t = per_rep_seconds(fwd_body)
        try:
            total_t = per_rep_seconds(bwd_body)  # grad includes a forward
            bwd_t = max(total_t - fwd_t, 0.1 * fwd_t)
        except Exception:
            bwd_t = 2.0 * fwd_t
        return fwd_t, bwd_t


def attach_measured_mode(cost_model, *, repeats: int = 50,
                         compute_dtype=None,
                         cache_path: Optional[str] = None) -> None:
    """Wire an OperatorMeasurer into a CostModel: every cost-cache miss
    first tries real silicon; NaN (unmeasurable) falls back to the
    analytic roofline. cache_path persists measurements across runs."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        warnings.warn(
            f"measured-search is timing ops on the '{backend}' backend; "
            "mixing those times with the machine model's TPU link costs "
            "skews the search — use for testing only"
        )
    cost_model.measure_fn = OperatorMeasurer(
        repeats=repeats, compute_dtype=compute_dtype, cache_path=cache_path
    )
