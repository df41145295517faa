"""Differential tests for the radix-select backend and the int32 tag
rebase.

Two contracts pinned here:

1. ``select_impl="radix"`` (histogram k-selection + [k]-sized sort)
   produces BIT-IDENTICAL decision ordering and post-state to
   ``select_impl="sort"`` (the original full sort) on every shape the
   selection can see: uniform and Zipf-skewed weights, all-ties,
   single-client, k past the live count, both dmClock regimes, and
   re-entry boundaries (driving a workload to exhaustion batch by
   batch).  The sort path itself is pinned to the serial engine by
   tests/test_prefix.py, so radix == sort == serial transitively; the
   direct radix-vs-serial check rides along anyway.

2. ``kernels.rebase32``/``restore64`` round-trip int64 tags bit-exactly
   within the +-(2^31 - 8) window (sentinels MAX_TAG/MIN_TAG map to
   reserved codes), report ``ok=False`` past it, and the
   ``tag_width=32`` epoch carry built on them is bit-identical to
   ``tag_width=64`` when the window holds -- and falls back EXACTLY
   (commits nothing, keeps the input state, bumps ``rebase_fallbacks``)
   when it does not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.core import ClientInfo
from dmclock_tpu.core.timebase import MAX_TAG, MIN_TAG, NS_PER_SEC
from dmclock_tpu.engine import kernels
from dmclock_tpu.engine.fastpath import (prefix_window_width,
                                         scan_calendar_epoch,
                                         scan_chain_epoch,
                                         scan_prefix_epoch,
                                         speculate_chain_batch,
                                         speculate_prefix_batch)
from dmclock_tpu.engine.kernels import rebase32, restore64

from engine_helpers import assert_states_equal, build_state, deep_state

S = NS_PER_SEC


def assert_batches_identical(a, b):
    """Sort-backend batch vs radix-backend batch: every caller-visible
    output must match bitwise (padding included -- the radix compaction
    promises sentinel-identical padding)."""
    assert int(a.count) == int(b.count)
    assert bool(a.guards_ok) == bool(b.guards_ok)
    da, db = jax.device_get(a.decisions), jax.device_get(b.decisions)
    for f in da._fields:
        assert np.array_equal(getattr(da, f), getattr(db, f)), \
            f"decision field {f} diverged"
    assert_states_equal(a.state, b.state)


def both_impls(state, now, k, **kw):
    a = speculate_prefix_batch(state, jnp.int64(now), k,
                               anticipation_ns=0, select_impl="sort",
                               **kw)
    b = speculate_prefix_batch(state, jnp.int64(now), k,
                               anticipation_ns=0, select_impl="radix",
                               **kw)
    assert_batches_identical(a, b)
    return b


def drive_both_to_exhaustion(state, now, k, *, max_batches=100, **kw):
    """Radix batch == sort batch == serial prefix at EVERY re-entry
    boundary until the workload drains."""
    allow = kw.get("allow_limit_break", False)
    st, total = state, 0
    for _ in range(max_batches):
        batch = both_impls(st, now, k, **kw)
        c = int(batch.count)
        if c:
            ser_state, _, ser = kernels.engine_run(
                st, jnp.int64(now), c, allow_limit_break=allow,
                anticipation_ns=0, advance_now=False)
            ser = jax.device_get(ser)
            d = jax.device_get(batch.decisions)
            assert np.array_equal(d.slot[:c], ser.slot)
            assert np.array_equal(d.phase[:c], ser.phase)
            assert_states_equal(batch.state, ser_state)
        st, total = batch.state, total + c
        if c == 0:
            break
    return st, total


# ----------------------------------------------------------------------
# radix vs sort: the differential shapes
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_radix_uniform_weights():
    infos = {c: ClientInfo(0, 1 + (c % 4), 0) for c in range(16)}
    state = deep_state(infos, depth=4)
    _, total = drive_both_to_exhaustion(state, 50 * S, 8)
    assert total == 16 * 4


@pytest.mark.slow
def test_radix_zipf_weights():
    """Zipf-skewed weights: the packed keys spread over decades, so
    every histogram round sees non-trivial digit distributions."""
    w = np.clip(64.0 / np.arange(1, 25) ** 1.1, 0.5, 64.0)
    rng = np.random.default_rng(3)
    rng.shuffle(w)
    infos = {c: ClientInfo(0, float(w[c]), 0) for c in range(24)}
    state = deep_state(infos, depth=3)
    _, total = drive_both_to_exhaustion(state, 200 * S, 16)
    assert total == 24 * 3


@pytest.mark.slow
def test_radix_all_ties():
    """Equal weights + equal arrivals: every selection boundary is a
    pure creation-order tie group -- the low 28 order bits decide."""
    infos = {c: ClientInfo(0, 2, 0) for c in range(12)}
    state = deep_state(infos, depth=6)
    _, total = drive_both_to_exhaustion(state, 8 * S, 8)
    assert total == 12 * 6


@pytest.mark.slow
def test_radix_single_client():
    infos = {0: ClientInfo(0, 1, 0)}
    adds = [(0, 1 * S, 1, 1, 1) for _ in range(10)]
    state = build_state(infos, adds, capacity=8)
    _, total = drive_both_to_exhaustion(state, 100 * S, 8)
    assert total == 10


def test_radix_k_past_live_count():
    """kk > live candidates: the KEY_INF exclusion must drop sentinel
    rows and pad the compaction identically to the trimmed sort."""
    infos = {c: ClientInfo(0, 1, 0) for c in range(3)}
    adds = [(c, 1 * S, 1, 1, 1) for c in range(3)]
    state = build_state(infos, adds, capacity=8)
    batch = both_impls(state, 1000 * S, 64)
    assert int(batch.count) == 3
    both_impls(batch.state, 1000 * S, 64)   # empty follow-up


@pytest.mark.slow
def test_radix_both_regimes():
    """Reservation backlog drains mid-run: batches cross the
    constraint->weight boundary; classes 0 and 1 both populated."""
    infos = {c: ClientInfo(2, 1, 0) for c in range(8)}
    state = deep_state(infos, depth=8)
    _, total = drive_both_to_exhaustion(state, 4 * S, 16)
    assert total == 8 * 8


@pytest.mark.slow
def test_radix_limit_break_class():
    """AtLimit::Allow adds class 2: limit-capped clients selected by
    effective proportion with the limit_break flag."""
    infos = {c: ClientInfo(0, 1, 0.5) for c in range(6)}
    state = deep_state(infos, depth=4)
    _, total = drive_both_to_exhaustion(state, 2 * S, 8,
                                        allow_limit_break=True)
    assert total == 6 * 4


def test_radix_chain_batch():
    """Chained units (chain_depth > 1): the lens column rides the small
    sort as a payload; unit stream must match bitwise."""
    infos = {c: ClientInfo(1, 2, 0) for c in range(6)}
    state = deep_state(infos, depth=10)
    now = jnp.int64(3 * S)
    a = speculate_chain_batch(state, now, 8, chain_depth=4,
                              anticipation_ns=0, select_impl="sort")
    b = speculate_chain_batch(state, now, 8, chain_depth=4,
                              anticipation_ns=0, select_impl="radix")
    assert int(a.count) == int(b.count)
    assert int(a.unit_count) == int(b.unit_count)
    for f in ("slot", "cls", "length"):
        assert np.array_equal(jax.device_get(getattr(a, f)),
                              jax.device_get(getattr(b, f))), f
    assert_states_equal(a.state, b.state)


@pytest.mark.slow
def test_radix_epoch_stream_identical():
    """Whole epochs under both backends: decision stream, guards, and
    final state bit-identical (the A/B contract benches rely on)."""
    infos = {c: ClientInfo(0, 1 + (c % 2), 0) for c in range(8)}
    state = deep_state(infos, depth=5)
    now = jnp.int64(30 * S)
    es = scan_prefix_epoch(state, now, 10, 8, anticipation_ns=0,
                           select_impl="sort")
    er = scan_prefix_epoch(state, now, 10, 8, anticipation_ns=0,
                           select_impl="radix")
    for f in ("count", "guards_ok", "slot", "phase", "cost", "lb"):
        assert np.array_equal(jax.device_get(getattr(es, f)),
                              jax.device_get(getattr(er, f))), f
    assert_states_equal(es.state, er.state)


def test_radix_kth_key_property():
    """_radix_kth_key == the kk-th smallest element of the array, over
    random non-negative int64 populations with duplicates."""
    from dmclock_tpu.engine.fastpath import _radix_kth_key

    rng = np.random.default_rng(11)
    for trial in range(4):
        n = int(rng.integers(5, 200))
        # mix magnitudes so high and low digit rounds both matter
        vals = rng.integers(0, 1 << int(rng.integers(4, 62)), size=n)
        vals = jnp.asarray(vals, dtype=jnp.int64)
        kk = int(rng.integers(1, n + 1))
        got = int(_radix_kth_key(vals, kk))
        want = int(np.sort(np.asarray(vals))[kk - 1])
        assert got == want, (trial, n, kk, got, want)


# ----------------------------------------------------------------------
# int32 rebase: round-trip property + epoch carry
# ----------------------------------------------------------------------

def test_rebase32_roundtrip_in_window():
    rng = np.random.default_rng(5)
    origin = jnp.int64(123_456_789_000)
    win = (1 << 31) - 8
    vals = rng.integers(-win + 1, win, size=256) + 123_456_789_000
    vals = np.concatenate([vals, [MAX_TAG, MIN_TAG,
                                  123_456_789_000 + win - 1,
                                  123_456_789_000 - win + 1]])
    v = jnp.asarray(vals, dtype=jnp.int64)
    v32, ok = rebase32(v, origin)
    assert bool(ok)
    assert v32.dtype == jnp.int32
    back = restore64(v32, origin)
    assert np.array_equal(np.asarray(back), vals)


def test_rebase32_out_of_window_flags():
    origin = jnp.int64(0)
    win = (1 << 31) - 8
    for bad in (win, -win, win + 12345, -(win + 99)):
        v = jnp.asarray([0, bad], dtype=jnp.int64)
        _, ok = rebase32(v, origin)
        assert not bool(ok), bad
    # sentinels alone never trip the window
    v = jnp.asarray([MAX_TAG, MIN_TAG], dtype=jnp.int64)
    _, ok = rebase32(v, origin)
    assert bool(ok)


def _high_rate_state(n=12, depth=6):
    """Per-serve tag advance ~1e6 ns: a whole small epoch drifts well
    inside the +-2^31 ns rebase window."""
    infos = {c: ClientInfo(2000, 1000 * (1 + c % 3), 0)
             for c in range(n)}
    return deep_state(infos, depth=depth)


def _low_rate_state(n=12, depth=6):
    """Per-serve tag advance ~1e9 ns: one batch of serves exits the
    window -- the fallback shape."""
    infos = {c: ClientInfo(2, 1 + (c % 3), 0) for c in range(n)}
    return deep_state(infos, depth=depth)


@pytest.mark.parametrize("select_impl", ["sort", "radix"])
@pytest.mark.slow
def test_tag32_epoch_bit_identical_in_window(select_impl):
    state = _high_rate_state()
    now = jnp.int64(4 * S)
    e64 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=64, select_impl=select_impl)
    e32 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=32, select_impl=select_impl)
    assert jax.device_get(e32.guards_ok).all()
    for f in ("count", "slot", "phase", "cost", "lb"):
        assert np.array_equal(jax.device_get(getattr(e64, f)),
                              jax.device_get(getattr(e32, f))), f
    assert_states_equal(e64.state, e32.state)


@pytest.mark.slow
def test_tag32_chain_and_calendar_epochs():
    state = _high_rate_state()
    now = jnp.int64(4 * S)
    c64 = scan_chain_epoch(state, now, 3, 8, chain_depth=4,
                           anticipation_ns=0, tag_width=64)
    c32 = scan_chain_epoch(state, now, 3, 8, chain_depth=4,
                           anticipation_ns=0, tag_width=32)
    for f in ("count", "unit_count", "slot", "cls", "length"):
        assert np.array_equal(jax.device_get(getattr(c64, f)),
                              jax.device_get(getattr(c32, f))), f
    assert_states_equal(c64.state, c32.state)

    k64 = scan_calendar_epoch(state, now, 2, steps=8,
                              anticipation_ns=0, tag_width=64)
    k32 = scan_calendar_epoch(state, now, 2, steps=8,
                              anticipation_ns=0, tag_width=32)
    assert np.array_equal(jax.device_get(k64.served),
                          jax.device_get(k32.served))
    assert jax.device_get(k32.progress_ok).all()
    assert_states_equal(k64.state, k32.state)


@pytest.mark.slow
def test_tag32_window_trip_falls_back_exactly():
    """The fallback contract: a mid-epoch window trip zeroes that batch
    and every later one, keeps the carry at the last good state, and
    bumps rebase_fallbacks ONCE; the caller reruns on tag_width=64 from
    the returned state and loses nothing."""
    state = _low_rate_state()
    now = jnp.int64(4 * S)
    e32 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=32, with_metrics=True)
    counts = jax.device_get(e32.count)
    guards = jax.device_get(e32.guards_ok)
    # once a batch trips, everything from it on is zeroed / not ok
    first_bad = int(np.argmax(~guards)) if not guards.all() \
        else len(guards)
    assert first_bad < len(guards), "shape was supposed to trip"
    assert (counts[first_bad:] == 0).all()
    assert not guards[first_bad:].any()
    assert (jax.device_get(e32.slot)[first_bad:] == -1).all()
    met = jax.device_get(e32.metrics)
    from dmclock_tpu.obs import device as obsdev
    assert met[obsdev.MET_REBASE_FALLBACKS] == 1
    # the returned state is the last good state: rerunning the epoch on
    # the int64 path from it must continue the EXACT serial stream
    e64_ref = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                                tag_width=64)
    ref_counts = jax.device_get(e64_ref.count)
    # batches before the trip match the int64 epoch bitwise
    assert np.array_equal(counts[:first_bad], ref_counts[:first_bad])
    st_resume = scan_prefix_epoch(e32.state, now, 4 - first_bad, 8,
                                  anticipation_ns=0, tag_width=64)
    assert np.array_equal(
        jax.device_get(st_resume.slot),
        jax.device_get(e64_ref.slot)[first_bad:])
    assert_states_equal(st_resume.state, e64_ref.state)


@pytest.mark.slow
def test_tag32_ignores_stale_inactive_lanes():
    """A stale lane (inactive, or active but empty) whose ancient tag
    sits far outside any window must NOT trip the int32 carry: it
    cannot serve this epoch, its fields are excluded from the fit, and
    the exit state carries its exact entry values."""
    state = _high_rate_state()
    n = state.capacity
    far = jnp.int64(1) << 40          # ~18 minutes of virtual time away
    state = state._replace(
        active=state.active.at[n - 1].set(False),
        head_prop=state.head_prop.at[n - 1].set(far),
        prev_prop=state.prev_prop.at[n - 1].set(-far),
        # an ACTIVE but drained lane is equally dead for the epoch
        depth=state.depth.at[n - 2].set(0),
        head_resv=state.head_resv.at[n - 2].set(far))
    now = jnp.int64(4 * S)
    e64 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=64)
    e32 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=32, with_metrics=True)
    assert jax.device_get(e32.guards_ok).all()
    from dmclock_tpu.obs import device as obsdev
    assert jax.device_get(e32.metrics)[obsdev.MET_REBASE_FALLBACKS] == 0
    for f in ("count", "slot", "phase", "cost"):
        assert np.array_equal(jax.device_get(getattr(e64, f)),
                              jax.device_get(getattr(e32, f))), f
    assert_states_equal(e64.state, e32.state)
    assert int(e32.state.head_prop[n - 1]) == int(far)
    assert int(e32.state.prev_prop[n - 1]) == -int(far)
    assert int(e32.state.head_resv[n - 2]) == int(far)


def test_tag32_dead_batches_do_not_pollute_metrics():
    """Post-trip dead batches force their counts to zero by contract;
    those zeros are a fallback artifact and must not read as
    limit_stalls, and the discarded speculative states must not feed
    the ring high-water mark."""
    state = _low_rate_state()
    now = jnp.int64(4 * S)
    e32 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=32, with_metrics=True)
    guards = jax.device_get(e32.guards_ok)
    assert not guards.all(), "shape was supposed to trip"
    met = jax.device_get(e32.metrics)
    from dmclock_tpu.obs import device as obsdev
    assert met[obsdev.MET_STALLS] == 0
    # hwm comes only from LIVE batches; the committed prefix of the
    # int64 reference epoch bounds it
    e64 = scan_prefix_epoch(state, now, 4, 8, anticipation_ns=0,
                            tag_width=64, with_metrics=True)
    assert met[obsdev.MET_RING_HWM] <= \
        jax.device_get(e64.metrics)[obsdev.MET_RING_HWM]


def test_tag32_initial_misfit_returns_input_state():
    """An epoch whose ENTRY state already cannot narrow must return the
    input state untouched with zero commits and one fallback bump."""
    state = _low_rate_state()
    # spread head_prop past the whole window so entry narrowing fails
    n = state.capacity
    spread = (jnp.arange(n, dtype=jnp.int64) * jnp.int64(1 << 28))
    state = state._replace(head_prop=state.head_prop + spread)
    now = jnp.int64(4 * S)
    e32 = scan_prefix_epoch(state, now, 3, 8, anticipation_ns=0,
                            tag_width=32, with_metrics=True)
    assert (jax.device_get(e32.count) == 0).all()
    assert not jax.device_get(e32.guards_ok).any()
    assert_states_equal(e32.state, state)
    from dmclock_tpu.obs import device as obsdev
    assert jax.device_get(e32.metrics)[obsdev.MET_REBASE_FALLBACKS] == 1


# ----------------------------------------------------------------------
# window_m chunked prefetch
# ----------------------------------------------------------------------

@pytest.mark.parametrize("window_m", [1, 2, 4, None])
def test_window_m_chunking_is_invisible(window_m):
    """Wide epochs chunk the ring prefetch; the decision stream, the
    final state and every telemetry accumulator must equal one m-row
    window's.  The derived default (None) runs on a ring narrow enough
    that ``prefix_window_width`` picks fewer rows than m, so the
    chunked path is the one under test."""
    from dmclock_tpu.obs import flight as obsflight
    from dmclock_tpu.obs import histograms as obshist

    m, ring = 16, 16
    assert prefix_window_width(m, ring, window_m=window_m) < m
    infos = {c: ClientInfo(0, 1 + (c % 3), 0) for c in range(10)}
    state = build_state(infos, [(c, S, 1, 1, 1) for _ in range(8)
                                for c in infos], ring=ring)
    now = jnp.int64(20 * S)

    def run(wm):
        return scan_prefix_epoch(
            state, now, m, 4, anticipation_ns=0, window_m=wm,
            with_metrics=True, hists=obshist.hist_zero(),
            ledger=obshist.ledger_zero(state.capacity),
            flight=obsflight.flight_init(64))

    ref, ch = run(m), run(window_m)
    assert (jax.device_get(ref.count) > 0).all()   # every batch serves
    for f in ("count", "guards_ok", "slot", "phase", "cost", "lb",
              "metrics", "hists", "ledger", "flight"):
        a, b = jax.device_get((getattr(ref, f), getattr(ch, f)))
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            np.array_equal, a, b)), f
    assert_states_equal(ref.state, ch.state)


# (m, q, rotate on Pallas) shapes the width rule is held to
_WIDTH_SHAPES = [(m, q, pallas) for m in (1, 6, 64, 98, 100, 200)
                 for q, pallas in ((16, False), (64, False), (64, True),
                                   (128, True))]


def test_prefix_window_width_cell_shape():
    """The benchmark cell's shape (m=100 batches, Q=128, Pallas rotate)
    gets the width calibrated on the chip."""
    assert prefix_window_width(100, 128, pallas=True) == 25


@pytest.mark.parametrize("m,q,pallas", _WIDTH_SHAPES)
def test_prefix_window_width_divides_m_within_ring(m, q, pallas):
    w = prefix_window_width(m, q, pallas=pallas)
    assert m % w == 0 and 1 <= w <= min(m, q)


@pytest.mark.parametrize("m", [29, 61, 127])
def test_prefix_window_width_prime_m_keeps_one_window(m):
    """A prime m above the optimum has only 1 and m to choose from: one
    prefetch per batch costs more than m - 1 selects, so it keeps m."""
    assert m > prefix_window_width(100, 128, pallas=True)
    assert prefix_window_width(m, 128, pallas=True) == m


@pytest.mark.parametrize("m", [1, 2, 5, 16, 20])
def test_prefix_window_width_small_m_is_one_window(m):
    """At or below the optimum the epoch keeps one m-row window: the
    program of small epochs is unchanged."""
    assert prefix_window_width(m, 128, pallas=True) == m


def test_prefix_window_width_explicit_window_m_overrides():
    assert prefix_window_width(100, 128, pallas=True, window_m=4) == 4
    assert prefix_window_width(100, 128, pallas=True, window_m=500) == 100


def test_window_m_must_divide_m():
    infos = {0: ClientInfo(0, 1, 0)}
    state = build_state(infos, [(0, 1 * S, 1, 1, 1)], capacity=8)
    with pytest.raises(AssertionError):
        scan_prefix_epoch(state, jnp.int64(S), 4, 8,
                          anticipation_ns=0, window_m=3)
