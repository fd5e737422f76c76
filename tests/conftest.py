"""Test configuration: force an 8-device virtual CPU mesh BEFORE any backend
initialization.

This is the TPU analog of the reference's fake-device trick
(tests/python/unittest/test_multi_device_exec.py uses mx.cpu(N) contexts):
multi-chip sharding paths are exercised on one box.  The platform is pinned
in code so a bare ``pytest`` works without ``JAX_PLATFORMS=cpu`` in the
environment; both options must be set before the first device query.
"""
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 "
        "(-m 'not slow')")


# Do NOT arm jax's persistent compilation cache here (cache_dirs.
# arm_compile_cache is for the programs that run on the chip).  On jaxlib
# 0.4.36 (XLA:CPU) a cache-DESERIALIZED executable returned different
# floating-point results than a fresh compile of the same HLO (a
# greedy-decoded token flipped).  Under jaxlib 0.9.0 that did not
# reproduce — test_decode, test_paged_serve and test_train_step pass cold
# and warm with every executable cached — but a test run must still not
# depend on what an earlier run left on disk, so cold compiles stay the
# price of reproducible runs.
