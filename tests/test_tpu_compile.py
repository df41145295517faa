"""Compile the main path's kernels for a DESCRIBED v5e (no chip needed).

Interpret mode on CPU proves the Pallas kernels' semantics but not that
the TPU compiler accepts them: a slice off the tiling, a VMEM overrun,
or a collective the chip cannot lower only shows up here.  Each test
lowers and compiles at the real width (100k clients) against
``topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")``
and, where a kernel should be, finds it in the compiled program
(``tpu_custom_call``).

The topology is described inside a fixture (never at import, in a
``skipif`` or in ``parametrize``): only the xdist worker that runs this
file loads libtpu.  Programs that ask ``jax.default_backend()`` at trace
time are steered with ``jax.default_device`` onto the described chip.
The persistent compile cache is off around the compiles (an entry
written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from dmclock_tpu.engine import init_state
from dmclock_tpu.engine import fastpath as FP
from dmclock_tpu.engine import kernels_pallas as KP
from dmclock_tpu.engine import stream as SM
from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.obs import histograms as obshist

N = 100_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), tree)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_rotate_kernel_compiles_at_100k(one_chip):
    """The ring-window row rotate, N=100k clients x Q=128 ring."""
    ring = jax.ShapeDtypeStruct((N, 128), jnp.int64, sharding=one_chip)
    q0 = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda r, q: FP._rotate_rows_pallas(r, q, 8)).lower(
            ring, q0).compile()
    assert _kernel_calls(compiled) > 0


def test_wheel_scan_compiles_at_100k(one_chip):
    """The wheel bucket scan, n=100k lanes x 3x256 buckets."""
    keys = jax.ShapeDtypeStruct((N,), jnp.int64, sharding=one_chip)
    slot = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda k, s: KP.wheel_scan_pallas(k, s, 3 * 256)).lower(
            keys, slot).compile()
    assert _kernel_calls(compiled) > 0


def test_wheel_stream_chunk_compiles_at_100k(topo, one_chip):
    """One whole fused stream chunk at 100k clients: the wheel calendar
    on the Pallas bucket scan, ring 128 (so the rotate runs too),
    ingest + ledger on -- chip_smoke.py's serve job."""
    st = _shapes(jax.eval_shape(lambda: init_state(N, 128)), one_chip)
    led = _shapes(jax.eval_shape(lambda: obshist.ledger_zero(N)),
                  one_chip)
    counts = jax.ShapeDtypeStruct((4, N), jnp.int32, sharding=one_chip)
    epoch0 = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    fn = SM.build_stream_chunk(
        engine="calendar", epochs=4, m=3, k=64, dt_epoch_ns=10 ** 8,
        waves=4, calendar_impl="wheel", ladder_levels=4,
        wheel_kernel="pallas", with_metrics=True)
    with jax.default_device(topo.devices[0]):
        compiled = jax.jit(fn).lower(st, epoch0, counts, None,
                                     led).compile()
    assert _kernel_calls(compiled) > 0
    # 100k clients fit one 16 GB chip with room to spare
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 << 30


def test_mesh_int64_max_reduce_compiles(topo):
    """The mesh plane's max-merged metric rows: TPU all-reduces lower
    only SUM for int64, so a plain ``lax.pmax`` is refused;
    ``pmax_i64`` must compile across the four chips."""
    mesh = Mesh(np.array(topo.devices), ("servers",))
    vec = jax.ShapeDtypeStruct(
        (4, obsdev.NUM_METRICS), jnp.int64,
        sharding=NamedSharding(mesh, P("servers")))
    fn = jax.shard_map(
        lambda v: obsdev.metrics_mesh_reduce(v[0], "servers"),
        mesh=mesh, in_specs=P("servers"), out_specs=P(),
        check_vma=False)
    compiled = jax.jit(fn).lower(vec).compile()
    assert "all-reduce" in compiled.as_text()
