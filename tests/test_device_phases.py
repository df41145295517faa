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
    # every phase but the calendar engine's pull-budget cut
    assert set(chunk_phases.values()) == set(CP.PHASES) - {"budget"}
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


def test_a_budgeted_calendar_chunk_names_the_cut_and_counts_it():
    """A serve-only wheel calendar chunk with a pull budget: the cut
    runs under the ``budget`` phase, and its two counters equal a host
    recount with the serial engine -- at each epoch's ``now``, the
    requests servable beyond the budget (one level of steps = ring
    follows every queued request, so the level finds them all).  Last
    in the file: its executable joins the ``stream.chunk`` family the
    tests above read."""
    import numpy as np

    from dmclock_tpu.obs import device as obsdev

    from engine_helpers import (budget_population, build_state,
                                serial_pop, servable_count)

    n, ring, budget, dt, epoch0 = 128, 8, 150, 10 ** 9, 2
    state = build_state(*budget_population(n, ring), capacity=n,
                        ring=ring)
    fn = SM.jit_stream_chunk(
        engine="calendar", epochs=3, m=1, k=ring, dt_epoch_ns=dt,
        waves=2, calendar_impl="wheel", ladder_levels=1,
        with_metrics=True, pull_budget=budget, ingest=False)
    out = fn(state, jnp.int64(epoch0), None)
    out.state.depth.block_until_ready()
    assert "budget" in set(CP.op_phases("stream.chunk").values())

    cuts = over = 0
    st = state
    for e in range(3):
        now = jnp.int64((epoch0 + e + 1) * dt)
        servable = int(servable_count(st, now))
        take = min(budget, servable)
        cuts += servable > budget
        over += max(servable - budget, 0)
        assert int(np.asarray(out.outs["count"][e]).sum()) == take
        st = serial_pop(st, now, take)
    assert cuts > 0
    met = np.asarray(out.outs["metrics"]).sum(axis=0)
    assert met.shape == (obsdev.NUM_METRICS_BUDGET,)
    named = obsdev.metrics_dict(met)
    assert named["budget_cut_epochs"] == cuts
    assert named["budget_left_servable"] == over
