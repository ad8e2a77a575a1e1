"""Test harness: single-host multi-device CPU mesh.

The reference tests all distributed behavior through local-mode Spark
(`local[*]`, SparkTestUtils.scala:61-77). The JAX analogue is an 8-device
virtual CPU platform: `xla_force_host_platform_device_count=8` set before
backend init, so sharded==unsharded numerics can be asserted without TPUs.

The platform is pinned to the CPU before any backend is initialized
(``JAX_PLATFORMS`` and the config value both), so the suite never takes a
chip, whatever the caller's environment says.

x64 is enabled so optimizer/loss tests can assert against closed forms at
tight tolerances; production TPU runs use f32/bf16.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: repeated runs skip recompiling the jit
# programs that dominate suite wall-clock. Safe to share across shards —
# entries are keyed by HLO hash. JAX_COMPILATION_CACHE_DIR, where set, wins
# (JAX reads it by itself); else PHOTON_TEST_CACHE_DIR, else a fixed test
# cache. Disable with PHOTON_TEST_CACHE_DIR=off.
_cache_dir = os.environ.get("PHOTON_TEST_CACHE_DIR", "/tmp/photon-jax-cache")
if _cache_dir.lower() != "off":
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

# Sanitizer analogue (SURVEY §5.2): PHOTON_DEBUG_NANS=1 makes every NaN
# produced inside a jit program raise at the producing op — the functional
# counterpart of the JVM's memory-safety guarantees the reference leans on.
if os.environ.get("PHOTON_DEBUG_NANS") == "1":
    jax.config.update("jax_debug_nans", True)
