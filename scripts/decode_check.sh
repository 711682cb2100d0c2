#!/usr/bin/env bash
# Disaggregated prefill/decode check (docs/serving.md): the decode
# objective must buy something and must be vetted like the training
# strategy. Two stages:
#   1. compile-both-objectives on 8- and 4-device CPU meshes: the
#      decode-searched strategy must DIFFER from the training one, the
#      decode cost model must rank it faster, and the static analyzer
#      (full FFA pass stack incl. FFA509, --fail-on error semantics)
#      must pass over BOTH strategies;
#   2. the decode suite (cost oracle units, paged-kernel parity,
#      batcher exactness, strategy round-trip) on both meshes.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

for n in 8 4; do
    echo "=== decode_check: compile both objectives, ${n}-device mesh ==="
    env JAX_NUM_CPU_DEVICES="$n" \
        XLA_FLAGS="--xla_force_host_platform_device_count=$n" \
        python - "$n" <<'EOF'
import sys

from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig, FFModel,
                          LossType, MetricsType, SGDOptimizer)
from flexflow_tpu.analysis.perf import perf_diagnostics
from flexflow_tpu.search import simulate_runtime

n = int(sys.argv[1])
cfg = FFConfig()
cfg.batch_size = 2
cfg.search_budget = 1
cfg.workersPerNode = n
m = FFModel(cfg)
ids = m.create_tensor((2, 16), DataType.DT_INT32)
t = m.embedding(ids, 29, 16, AggrMode.AGGR_MODE_NONE)
t = m.multihead_attention(t, t, t, 16, 2, causal=True)
t = m.dense(t, 16, ActiMode.AC_MODE_RELU)
t = m.softmax(m.dense(t, 29))
m.compile(SGDOptimizer(lr=0.01),
          LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
          [MetricsType.METRICS_ACCURACY])
m.compile_decode()

train = sorted(tuple(v.dim) for v in m.searched_views.values())
dec = sorted(tuple(v.dim) for v in m.decode_searched_views.values())
assert dec != train, f"decode search found the training strategy: {dec}"
cm = m._build_cost_model(objective="decode")
t_train = simulate_runtime(m.graph, m.searched_views, cm)
t_dec = simulate_runtime(m.decode_graph, m.decode_searched_views, cm)
assert t_dec < t_train, (t_dec, t_train)

for label, graph, views, objective in (
    ("train", m.graph, m.searched_views, "train"),
    ("decode", m.decode_graph, m.decode_searched_views, "decode"),
):
    rep = perf_diagnostics(graph, views=views,
                           cost_model=m._build_cost_model(objective=objective),
                           num_devices=n, objective=objective)
    assert not rep.errors, (
        f"{label} strategy has analyzer errors: "
        + "; ".join(d.format() for d in rep.errors))
    print(f"decode_check[{n}dev] {label}: {len(rep.warnings)} warnings, "
          f"0 errors")
print(f"decode_check[{n}dev]: decode {t_dec:.3e}s vs train-strategy "
      f"{t_train:.3e}s under the decode objective — OK")
EOF

    echo "=== decode_check: decode suite, ${n}-device mesh ==="
    env JAX_NUM_CPU_DEVICES="$n" \
        XLA_FLAGS="--xla_force_host_platform_device_count=$n" \
        python -m pytest tests/test_decode_search.py -q -p no:cacheprovider
done

echo "decode_check: OK"
