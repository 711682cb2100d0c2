"""Runtime configuration.

TPU-native analogue of the reference FFConfig (include/flexflow/config.h:92-160,
parse_args src/runtime/model.cc:3556). Instead of Legion `-ll:gpu` worker
counts, we describe a TPU mesh: number of chips visible to this process plus a
logical multi-host topology for the strategy search. Flags keep the reference's
spellings so reference launch scripts port over directly.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import List, Optional

import jax

from .ff_types import CompMode


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache, once, where the program
    first needs JAX (FFConfig()), and return the directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it: nothing
    is set in code. Otherwise the cache is `<checkout>/.jax_cache`,
    resolved from this package's own location — a fixed path, because a
    directory that moves between runs (a tempdir, a pid, a timestamp)
    never hits — and every executable is kept, so that a second run of
    the same program compiles nothing and adds nothing."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclasses.dataclass
class FFConfig:
    """Global run configuration.

    Mirrors reference config.h:92-160 field-for-field where meaningful on TPU;
    `workersPerNode` counts TPU chips instead of GPUs.
    """

    epochs: int = 1
    batch_size: int = 64
    numNodes: int = 1
    workersPerNode: int = 0  # 0 = all visible devices
    cpusPerNode: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # Strategy-search knobs (reference config.h:128-160)
    search_budget: int = -1
    search_alpha: float = 1.2
    # Cost-model side of comm/compute overlap: when True the search costs
    # overlappable collectives (weight-grad syncs that are statically
    # independent of the backward critical path) at
    # max(0, comm - hideable_compute) instead of additively, so it
    # PREFERS strategies whose collectives hide
    # (search/cost_model.py; analysis/collectives.overlappable_grad_syncs
    # is the static proof). Off by default so searched strategies stay
    # reproducible against earlier rounds; --overlap-backward-update
    # turns both sides on.
    search_overlap_backward_update: bool = False
    # Slice-loss survivability bias (search/survivability.py): on
    # hierarchical multi-slice machines the search multiplies a
    # candidate's cost by 1 + penalty * (fraction of weight bytes whose
    # shards cross the slice boundary), preferring strategies where only
    # data-parallel replicas span slices — a preemption then shrinks the
    # run instead of forcing a full reshard (FFA601 lints what remains).
    # -1.0 = auto: 0.25 on hierarchical multi-node machines, 0 elsewhere.
    # 0 disables; larger = stronger preference (still not a hard
    # constraint — a cross-slice strategy that is MUCH faster per step
    # can outbid the penalty).
    search_survivability_penalty: float = -1.0
    # Executed-step side (reference config.h:133 overlap_backward_update):
    # decompose the data-parallel gradient all-reduce into per-weight
    # reduce-scatter + sharded optimizer update + all-gather of updated
    # params, so each layer's collective overlaps earlier layers'
    # backward matmuls and optimizer state shards ZeRO-1 style
    # (parallel/executor.py set_overlap_grad_sync). Numerically
    # equivalent to the all-reduce step; on by default (inert on a
    # single chip / data degree 1).
    overlap_backward_update: bool = True
    computationMode: CompMode = CompMode.COMP_MODE_TRAINING
    only_data_parallel: bool = False
    enable_sample_parallel: bool = True
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    enable_inplace_optimizations: bool = False
    # TPU addition: sequence/context parallelism as a first-class strategy
    enable_sequence_parallel: bool = False
    # Manual strategy degrees (no-search path). data_parallel_degree 0 =
    # fill remaining devices. The Unity search overrides these.
    tensor_parallel_degree: int = 1
    sequence_parallel_degree: int = 1
    # Pipeline parallelism (TPU addition — the reference's OP_PIPELINE is
    # enum-only): stages for transformer_blocks stacks, and microbatches
    # per pipeline flush (0 = one per stage).
    pipeline_parallel_degree: int = 1
    num_microbatches: int = 0
    # FSDP/ZeRO weight sharding (parallel/weight_sharding.py): shard
    # parameters + optimizer state this many ways over the "fsdp" mesh
    # axis, carved out of the data-parallel workers (must divide the data
    # degree; clamped otherwise). 1 = fully replicated weights (the old
    # behavior). The Unity memory-lambda search can also introduce weight
    # sharding on its own (search/substitution.py fsdp_shard_weights).
    fsdp_degree: int = 1
    # Recompute memory-heavy op internals (attention scores/probs) in the
    # backward instead of saving them (jax.checkpoint). Exact math; trades
    # FLOPs for HBM. Off by default — at benchmark shapes the stored-probs
    # backward is faster (measured 316 vs 245 samples/s at seq 512); turn
    # on for long sequences / big models where residuals exceed HBM.
    remat: bool = False
    expert_parallel_degree: int = 1
    # bf16 compute with f32 master weights (TPU-native mixed precision).
    # Off by default so numerical-alignment tests match f32 references;
    # benchmarks turn it on.
    allow_mixed_precision: bool = False
    # Store gradients in bf16 under mixed precision (the standard AMP
    # recipe: half-width grad store + f32 master weights; the f32->bf16
    # convert fuses into the grad matmuls' epilogues). Measured
    # single-chip-neutral on the Transformer bench (XLA already fuses the
    # f32 grad path); the win is cross-chip grad reduce-scatters riding
    # ICI/DCN at half width. None = follow allow_mixed_precision; set
    # False to force f32 gradient storage.
    bf16_grads: Optional[bool] = None
    # End-to-end static drift budget (analysis/precision.py FFA705): the
    # accumulated ulp-scaled quantization error a searched strategy may
    # statically incur along its longest path. None = the pass default
    # (precision.DEFAULT_DRIFT_BUDGET). runtime/verify.py derives the
    # differential verifier's per-dtype tolerances from the SAME budget
    # (tolerance_from_budget), so tightening it makes both the static
    # lint and the runtime check stricter together.
    precision_drift_budget: Optional[float] = None
    simulator_work_space_size: int = 64 * 1024 * 1024
    search_num_nodes: int = -1
    search_num_workers: int = -1
    base_optimize_threshold: int = 10
    enable_control_replication: bool = True
    python_data_loader_type: int = 2
    perform_fusion: bool = False
    profiling: bool = False
    # Unity search costs ops by on-device microbenchmarks instead of the
    # analytic roofline (reference: the Simulator always measures,
    # simulator.cc:489; here it's opt-in because it pays real compiles)
    measure_operator_costs: bool = False
    # persist measured-search microbenchmarks across runs (reference: the
    # Simulator's cached measurements); empty = in-memory only
    measured_cache_path: str = ""
    export_strategy_file: str = ""
    import_strategy_file: str = ""
    export_strategy_computation_graph_file: str = ""
    substitution_json_path: Optional[str] = None
    machine_model_version: int = 0
    machine_model_file: str = ""
    simulator_segment_size: int = 16777216
    simulator_max_num_segments: int = 1
    enable_propagation: bool = False
    perform_memory_search: bool = False
    device_mem: int = 0  # bytes of HBM per chip for the memory-aware search
    seed: int = 0
    iterations: int = 1
    # Steps fused into one XLA dispatch by fit() (lax.scan driver — the
    # Legion trace-replay analog). 1 = one host dispatch per batch.
    iterations_per_dispatch: int = 1

    def __post_init__(self):
        enable_compile_cache()
        # a backend that cannot initialise raises here, at the first line
        # of every program, instead of being counted as one worker
        if self.workersPerNode == 0:
            self.workersPerNode = max(1, jax.local_device_count())
        if self.numNodes == 1:
            # multi-host (runtime/distributed.py): one "node" per
            # process, like the reference's one-Legion-rank-per-host
            self.numNodes = max(1, jax.process_count())
        argv = sys.argv[1:]
        if argv:
            self.parse_args(argv)

    # -- reference: model.cc:3556 parse_args ------------------------------
    def parse_args(self, argv: List[str]) -> None:
        i = 0
        take = lambda: argv[i + 1]  # noqa: E731
        while i < len(argv):
            a = argv[i]
            try:
                if a in ("-e", "--epochs"):
                    self.epochs = int(take()); i += 1
                elif a in ("-b", "--batch-size"):
                    self.batch_size = int(take()); i += 1
                elif a == "--lr" or a == "-lr":
                    self.learning_rate = float(take()); i += 1
                elif a == "--wd" or a == "-wd":
                    self.weight_decay = float(take()); i += 1
                elif a in ("-p", "--print-freq"):
                    i += 1
                elif a in ("-ll:gpu", "-ll:tpu"):
                    self.workersPerNode = int(take()); i += 1
                elif a == "-ll:cpu":
                    self.cpusPerNode = int(take()); i += 1
                elif a == "--nodes":
                    self.numNodes = int(take()); i += 1
                elif a == "--budget" or a == "--search-budget":
                    self.search_budget = int(take()); i += 1
                elif a == "--alpha" or a == "--search-alpha":
                    self.search_alpha = float(take()); i += 1
                elif a == "--only-data-parallel":
                    self.only_data_parallel = True
                elif a == "--enable-parameter-parallel":
                    self.enable_parameter_parallel = True
                elif a == "--enable-attribute-parallel":
                    self.enable_attribute_parallel = True
                elif a == "--enable-sequence-parallel":
                    self.enable_sequence_parallel = True
                elif a == "--fusion":
                    self.perform_fusion = True
                elif a == "--profiling":
                    self.profiling = True
                elif a == "--measured-search":
                    self.measure_operator_costs = True
                elif a == "--measured-cache":
                    self.measured_cache_path = take(); i += 1
                elif a == "--search-num-nodes":
                    self.search_num_nodes = int(take()); i += 1
                elif a == "--search-num-workers":
                    self.search_num_workers = int(take()); i += 1
                elif a == "--export" or a == "--export-strategy":
                    self.export_strategy_file = take(); i += 1
                elif a == "--import" or a == "--import-strategy":
                    self.import_strategy_file = take(); i += 1
                elif a == "--memory-search":
                    self.perform_memory_search = True
                elif a == "--overlap-backward-update":
                    self.overlap_backward_update = True
                    self.search_overlap_backward_update = True
                elif a == "--no-overlap-backward-update":
                    self.overlap_backward_update = False
                    self.search_overlap_backward_update = False
                elif a == "--fsdp-degree":
                    self.fsdp_degree = int(take()); i += 1
                elif a == "--machine-model-version":
                    self.machine_model_version = int(take()); i += 1
                elif a == "--machine-model-file":
                    self.machine_model_file = take(); i += 1
                elif a == "--substitution-json":
                    self.substitution_json_path = take(); i += 1
                elif a == "--simulator-workspace-size":
                    self.simulator_work_space_size = int(take()); i += 1
                elif a == "--iterations":
                    self.iterations = int(take()); i += 1
                elif a == "--iterations-per-dispatch":
                    self.iterations_per_dispatch = int(take()); i += 1
                # silently skip unknown flags (Legion-style passthrough)
            except (IndexError, ValueError):
                pass
            i += 1

    # snake_case aliases matching the reference cffi property names
    # (flexflow_cffi.py:526 FFConfig.batch_size/workers_per_node/num_nodes),
    # so `from flexflow.core import *` scripts read config fields verbatim.
    @property
    def workers_per_node(self) -> int:
        if self.workersPerNode > 0:
            return self.workersPerNode
        return len(jax.devices())

    @property
    def num_nodes(self) -> int:
        return self.numNodes

    @property
    def cpus_per_node(self) -> int:
        return self.cpusPerNode

    @property
    def numWorkers(self) -> int:
        """Total chips in the (possibly hypothetical) machine."""
        if self.search_num_nodes > 0 and self.search_num_workers > 0:
            return self.search_num_nodes * self.search_num_workers
        return self.numNodes * self.workersPerNode

    # getter-method spellings used by older reference scripts
    # (bootcamp_demo/ff_alexnet_cifar10.py calls ffconfig.get_batch_size()
    # etc., predating the cffi property API at flexflow_cffi.py:536-549)
    def get_batch_size(self) -> int:
        return self.batch_size

    def get_epochs(self) -> int:
        return self.epochs

    def get_workers_per_node(self) -> int:
        return self.workers_per_node

    def get_num_nodes(self) -> int:
        return self.num_nodes

    def get_current_time(self) -> float:
        import time

        return time.time() * 1e6  # microseconds, like Realm::Clock

    def begin_trace(self, trace_id: int) -> None:
        """reference: flexflow_cffi.py:2093 (Legion trace capture around a
        training iteration). XLA's compiled-executable cache plays that
        role here — the first jitted call traces, later ones replay — so
        these are accepted no-ops for drop-in script compat."""

    def end_trace(self, trace_id: int) -> None:
        """See begin_trace."""


@dataclasses.dataclass
class FFIterationConfig:
    """Per-iteration config (reference: config.h:162-167)."""

    seq_length: int = -1

    def reset(self):
        self.seq_length = -1
