"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware; the CPU pin holds even when the
environment does not set ``JAX_PLATFORMS=cpu`` (tests/test_tpu_compile.py
compiles for a DESCRIBED v5e, which needs no attached chip).  x64 stays
enabled because the canonical tag algebra is int64 nanoseconds.
"""

import gc
import os

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
# entry points called in-process (dmc_sim.main) and the children tests
# spawn (net.serve, the supervisor) turn the persistent compile cache
# on at <repo>/.jax_cache/; a test run must not write it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """One long pytest process accumulates XLA CPU compile state until
    late-suite tests stall for tens of minutes or the compiler
    segfaults (observed at ~140 tests in).  Dropping every compiled
    program between modules keeps each module's footprint fresh; the
    shared-kernel recompiles this forces are far cheaper than the
    stall."""
    yield
    jax.clear_caches()
    # the compile plane's instrumented caches hold AOT executables
    # OUTSIDE jax's own caches -- drop those too, or the relief this
    # fixture exists for never reaches the module jit caches
    from dmclock_tpu.obs import compile_plane
    compile_plane.clear_compiled()
    gc.collect()
