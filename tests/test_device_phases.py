"""Device phases of the stream chunk (obs.compile_plane.PHASES):
every phase scope reaches the compiled program's instruction metadata,
and ``op_phases`` maps the chunk's instructions back to them."""

import jax.numpy as jnp
import pytest

from dmclock_tpu.engine import init_state
from dmclock_tpu.engine import stream as SM
from dmclock_tpu.obs import compile_plane as CP


@pytest.fixture(scope="module")
def chunk_phases():
    """A tiny prefix chunk (ingest on, metrics on) run once through
    the instrumented jit cache, so its executable is live."""
    fn = SM.jit_stream_chunk(
        engine="prefix", epochs=2, m=2, k=8, dt_epoch_ns=10 ** 8,
        waves=2, allow_limit_break=True, with_metrics=True)
    out = fn(init_state(64, 16), jnp.int64(0),
             jnp.ones((2, 64), jnp.int32))
    out.state.depth.block_until_ready()
    return CP.op_phases("stream.chunk")


def test_every_phase_names_instructions_of_the_chunk(chunk_phases):
    assert set(chunk_phases.values()) == set(CP.PHASES)
    assert all(name.startswith("%") for name in chunk_phases)


def test_an_unknown_phase_raises():
    with pytest.raises(ValueError, match="unknown device phase"):
        CP.phase_scope("sort")


def test_the_map_is_empty_with_the_compile_plane_off(chunk_phases):
    pl = CP.plane()
    was = pl.enabled
    pl.enable(False)
    try:
        assert CP.op_phases("stream.chunk") == {}
    finally:
        pl.enable(was)
    assert CP.op_phases("stream.chunk") == chunk_phases
