"""Test config: run everything on a virtual 8-device CPU mesh so sharding
paths are exercised without TPU hardware (SURVEY §4: substitutes for the
reference's no-cluster gap; the reference needs real GPUs for most tests).

The suite is a CPU suite wherever it runs: the platform is pinned here,
for this process and for the subprocess-launching tests (example smoke
tests) that inherit the environment. What runs on the chip is
`python chip_smoke.py`, not pytest.
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ.get("JAX_NUM_CPU_DEVICES", "8")
    ).strip()

# Persistent XLA compilation cache: the suite's wall clock is dominated by
# recompiling the same tiny models on this 1-core host; cache hits make
# repeat runs (and the example-script subprocesses, which inherit the env
# var) skip XLA entirely. Safe to delete the dir at any time. Placed
# through the environment variable, which the library's own helper
# (flexflow_tpu/config.py enable_compile_cache) leaves alone when set.
_CACHE_DIR = os.path.join(os.path.dirname(__file__), ".jax_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE_DIR)
# exported (not just config.update) so example-script subprocesses cache
# their sub-second compiles too
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# JAX_NUM_CPU_DEVICES overrides the 8-device default so sweeps can vary
# the PROCESS-level topology (scripts/elastic_check.sh runs the elastic
# suite on 8/4/2-device meshes; device-count-specific tests skip)
jax.config.update(
    "jax_num_cpu_devices", int(os.environ.get("JAX_NUM_CPU_DEVICES", "8"))
)
# jax may have been imported (and read its environment) before this file
jax.config.update(
    "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"]
)
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
)
