"""Static per-device HBM-fit analysis.

Computes a peak per-device memory estimate for a placed strategy from
material tensor shapes alone — no simulator profiling, no device time:
each op's shard bytes (inputs + outputs as the backward residual stash,
weights under the training multiplier `1 + grad_ratio +
optimizer.state_slots_per_weight()`) land on the devices of its
MachineView (or on every device when unplaced, i.e. replicated SPMD).
Strategies that cannot fit are rejected before the simulator or the
executor ever touches them.

Codes: FFA301 over budget (error), FFA302 usage report (info),
FFA303 measured reconciliation (info/warning — the step observatory's
live watermarks audited against this module's static prediction,
``memory_reconciliation_diagnostics``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from .diagnostics import AnalysisReport, Severity


def training_weight_multiplier(optimizer=None,
                               grad_bytes_ratio: float = 1.0) -> float:
    """Weight-sized allocations held per parameter during training
    (mirrors search.memory_optimization.weight_bytes_multiplier, without
    importing the search stack): master weight + gradient buffer +
    optimizer state slots."""
    slots = 0
    if optimizer is not None:
        get = getattr(optimizer, "state_slots_per_weight", None)
        slots = get() if get is not None else 0
    return 1.0 + grad_bytes_ratio + slots


def _shard_bytes(t) -> int:
    deg = max(1, t.get_total_degree())
    return (t.get_volume() // deg) * t.data_type.size


def estimate_per_device_bytes(
    graph,
    views: Optional[Dict] = None,
    num_devices: int = 1,
    *,
    train: bool = True,
    optimizer=None,
    grad_bytes_ratio: float = 1.0,
) -> Dict[int, int]:
    """device id -> estimated peak bytes for the placed strategy.

    The training multiplier (grads + optimizer slots) is resolved lazily,
    only when an op actually carries weights: weight-less ops (parallel
    ops in particular) contribute zero state bytes silently — resolving
    it eagerly made the PR-1 missing-``state_slots_per_weight``-hook
    warning fire spuriously on graphs with nothing to charge.

    Sharded weights divide by their degree via ``_shard_bytes``: an
    FSDP/ZeRO weight (parallel/weight_sharding.py) therefore charges
    ``bytes/degree x (1 + grad + slots)`` per device — the gradient
    buffer and the optimizer state shard with the parameter. An op inside
    a loop region (FFModel.loop) holds its weights once and, in training,
    its activations once a step."""
    views = views or {}
    wmul: Optional[float] = None
    per_dev: Dict[int, int] = {}
    all_devs = list(range(max(1, num_devices)))
    for op in graph.ops:
        act = sum(_shard_bytes(t) for t in op.inputs)
        act += sum(_shard_bytes(t) for t in op.outputs)
        if train and getattr(op, "loop", None) is not None:
            # a loop region keeps its ops' activations a step for the
            # backward; its weights are held once
            act *= op.loop.steps
        wb = 0
        if op.weights:
            if wmul is None:
                wmul = (training_weight_multiplier(optimizer,
                                                   grad_bytes_ratio)
                        if train else 1.0)
            wb = int(sum(_shard_bytes(w) for w in op.weights) * wmul)
        view = views.get(op.guid) or op.machine_view
        devs = view.device_ids() if view is not None else all_devs
        share = act + wb
        for d in devs:
            per_dev[d] = per_dev.get(d, 0) + share
    return per_dev


def memory_diagnostics(
    graph,
    views: Optional[Dict] = None,
    num_devices: int = 1,
    hbm_bytes: Optional[int] = None,
    *,
    train: bool = True,
    optimizer=None,
    grad_bytes_ratio: float = 1.0,
) -> Tuple[AnalysisReport, Dict[int, int]]:
    rep = AnalysisReport()
    per_dev = estimate_per_device_bytes(
        graph, views, num_devices, train=train, optimizer=optimizer,
        grad_bytes_ratio=grad_bytes_ratio,
    )
    if not per_dev:
        return rep, per_dev
    peak_dev = max(per_dev, key=per_dev.get)
    peak = per_dev[peak_dev]
    mib = 1024.0 ** 2
    if hbm_bytes:
        rep.add(
            Severity.INFO, "FFA302",
            f"static peak HBM estimate: {peak / mib:.1f} MiB on device "
            f"{peak_dev} (budget {hbm_bytes / mib:.1f} MiB, "
            f"{len(per_dev)} device(s) used)",
        )
        if peak > hbm_bytes:
            rep.add(
                Severity.ERROR, "FFA301",
                f"strategy cannot fit: device {peak_dev} needs "
                f"{peak / mib:.1f} MiB of {hbm_bytes / mib:.1f} MiB HBM "
                "(weights x (1 + grad + optimizer slots) + activation "
                "stash, from material shapes)",
                fix_hint="shard further / add devices, enable "
                         "perform_memory_search, or reduce batch size",
            )
    else:
        rep.add(
            Severity.INFO, "FFA302",
            f"static peak HBM estimate: {peak / mib:.1f} MiB on device "
            f"{peak_dev} ({len(per_dev)} device(s) used; no budget given)",
        )
    return rep, per_dev


def memory_reconciliation_diagnostics(
    static_per_dev: Dict[int, int],
    measured_per_dev: Dict[int, int],
    *,
    source: str = "memory_stats",
) -> Tuple[AnalysisReport, Optional[float]]:
    """The measured counterpart of FFA301/FFA302: reconcile the step
    observatory's live per-device watermarks (obs/step_profile.
    HbmSampler) against this module's static prediction. Returns the
    report plus the accuracy ratio static_peak / measured_peak
    (``ff_hbm_static_accuracy``): >1 means the static model
    over-provisions (safe, but it rejects strategies that would fit);
    <1 means it UNDER-predicts — the direction that passes the FFA301
    gate and then OOMs on device, reported as a WARNING. The
    ``live_arrays`` source is an allocator estimate (it cannot see XLA
    scratch), so under-prediction against it is still reported but the
    message says which oracle measured."""
    rep = AnalysisReport()
    static_peak = max(static_per_dev.values(), default=0)
    measured_peak = max(measured_per_dev.values(), default=0)
    if static_peak <= 0 or measured_peak <= 0:
        rep.add(
            Severity.INFO, "FFA303",
            "HBM reconciliation skipped: "
            + ("no static estimate" if static_peak <= 0
               else "no measured watermark")
            + f" (source {source})",
        )
        return rep, None
    ratio = static_peak / measured_peak
    mib = 1024.0 ** 2
    rep.add(
        Severity.INFO, "FFA303",
        f"measured peak HBM {measured_peak / mib:.1f} MiB vs static "
        f"estimate {static_peak / mib:.1f} MiB — static accuracy "
        f"{ratio:.2f} ({source}, {len(measured_per_dev)} device(s))",
    )
    if ratio < 0.9:
        rep.add(
            Severity.WARNING, "FFA303",
            f"the static model under-predicts peak HBM by "
            f"{(measured_peak - static_peak) / mib:.1f} MiB "
            f"(accuracy {ratio:.2f}) — a strategy can pass the FFA301 "
            "budget gate and still OOM on device",
            fix_hint="raise the activation-stash accounting "
                     "(estimate_per_device_bytes) or lower the budget "
                     "headroom the search plans against",
        )
    return rep, ratio
