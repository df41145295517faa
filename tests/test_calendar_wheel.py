"""Differential tests for the timer-wheel calendar engine.

``calendar_batch_wheel`` promises: the committed set, counters, and
final state are BIT-identical to ``calendar_batch_bucketed`` at the
same ``levels`` (and therefore to the serial engine -- the bucketed
suite pins that leg), with the ladder boundaries read from a
maintained [3, B] bucket-min index instead of dense [N] rebuilds.
The wheel-specific contracts pinned here:

- **adjust == rebuild**: ``wheel_adjust`` over exactly the clients
  whose (class, key) changed -- a fixed-now commit's served set, a
  live QoS update's target, an idle re-entry, a churn boundary
  re-slot -- equals ``wheel_build`` of the new state bit for bit;
- **first-occupied-bucket min == dense masked min** for entry packs
  (``wheel_origins``) and stop packs (``_wheel_stop_min``), the
  exactness identity the whole engine rests on (the bucket index is
  monotone in the key, so geometry affects discrimination only);
- **Pallas parity**: ``wheel_kernel="pallas"`` under
  ``DMCLOCK_WHEEL_INTERPRET=1`` is bit-identical to the XLA kernel,
  and off-TPU without interpret mode (or past the kernel's lane
  budget) it raises instead of running the reference in its place.

Compile-heavy shapes carry ``@pytest.mark.slow`` (the tier-1 budget
discipline of test_calendar_bucketed.py); scripts/run_tests.sh and
the ci.sh wheel smoke run everything.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.core import ClientInfo
from dmclock_tpu.core.timebase import NS_PER_SEC
from dmclock_tpu.engine import fastpath as FP
from dmclock_tpu.engine import kernels

from engine_helpers import assert_states_equal, deep_state
from test_calendar_bucketed import (_JIT, ladder_batch, minstop_batch,
                                    zipf64_state)
from test_prefix import mixed_qos_state, serial_run_lb

S = NS_PER_SEC


def wheel_batch(state, now, steps, levels, *, allow=False,
                wheel_kernel="xla"):
    key = ("wheel", state.capacity, state.ring_capacity, steps,
           levels, allow, wheel_kernel)
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(
            FP.calendar_batch_wheel, steps=steps, levels=levels,
            anticipation_ns=0, allow_limit_break=allow,
            wheel_kernel=wheel_kernel))
    return _JIT[key](state, jnp.int64(now))


_BATCH_FIELDS = ("count", "resv_count", "units", "served",
                 "served_resv", "lb", "progress_ok", "level_count",
                 "level_bound", "level_stall", "served_cost")


def assert_batches_equal(a, b):
    for f in _BATCH_FIELDS:
        assert bool(jnp.array_equal(getattr(a, f), getattr(b, f))), \
            f"wheel batch field {f} diverged"
    assert_states_equal(a.state, b.state)


def check_wheel_vs_serial(state, now, steps, levels, *, allow=False):
    """One wheel batch vs the serial engine for ``count`` steps (the
    test_calendar_bucketed differential, on the wheel path)."""
    b = wheel_batch(state, now, steps, levels, allow=allow)
    c = int(b.count)
    if c == 0:
        assert_states_equal(b.state, state)
        return b.state, 0
    ser_state, ser = serial_run_lb(state, now, c, allow)
    assert (ser.type == kernels.RETURNING).all()
    served = np.zeros(state.capacity, np.int32)
    np.add.at(served, ser.slot, 1)
    assert np.array_equal(served, jax.device_get(b.served))
    assert_states_equal(b.state, ser_state)
    return b.state, c


# ----------------------------------------------------------------------
# batch differentials: wheel == bucketed == serial
# ----------------------------------------------------------------------

def test_wheel_matches_bucketed_bitwise():
    """The headline batch gate: wheel == bucketed on every output
    field and the full state, driven over successive batches of the
    cfg4 cutter shape."""
    st_w = st_b = zipf64_state(n=10, depth=32)
    committed = 0
    for _ in range(3):
        bw = wheel_batch(st_w, 500 * S, 8, 3)
        bb = ladder_batch(st_b, 500 * S, 8, 3)
        assert_batches_equal(bw, bb)
        committed += int(bw.count)
        st_w, st_b = bw.state, bb.state
    assert committed > 0


def test_wheel_matches_serial():
    st, c = check_wheel_vs_serial(zipf64_state(n=10, depth=32),
                                  500 * S, 8, 2)
    assert c > 0
    check_wheel_vs_serial(st, 500 * S, 8, 2)


@pytest.mark.slow
def test_wheel_l1_bit_identical_to_minstop():
    """levels=1 wheel == the minstop calendar batch bit for bit (the
    ci.sh wheel-L1 composition gate's unit form)."""
    for state, now in ((zipf64_state(n=8, depth=16), 500 * S),
                       mixed_qos_state(n=8, depth=10)):
        st_m, st_w = state, state
        for _ in range(3):
            bm = minstop_batch(st_m, now, 6)
            bw = wheel_batch(st_w, now, 6, 1)
            assert int(bm.count) == int(bw.count)
            for f in ("units", "served", "served_resv", "lb"):
                assert np.array_equal(
                    jax.device_get(getattr(bm, f)),
                    jax.device_get(getattr(bw, f))), f
            assert_states_equal(bm.state, bw.state)
            st_m, st_w = bm.state, bw.state


@pytest.mark.slow
def test_wheel_mixed_regimes_and_allow():
    """Interleaved constraint/weight regimes and AtLimit::Allow ride
    the wheel exactly (vs serial AND vs bucketed)."""
    state, now = mixed_qos_state(n=8, depth=12)
    st = state
    for _ in range(4):
        st, c = check_wheel_vs_serial(st, now, 6, 3)
        if c == 0:
            break
    st_w = st_b = state
    for _ in range(3):
        bw = wheel_batch(st_w, now, 6, 3, allow=True)
        bb = ladder_batch(st_b, now, 6, 3, allow=True)
        assert_batches_equal(bw, bb)
        st_w, st_b = bw.state, bb.state


# ----------------------------------------------------------------------
# in-place adjust == rebuild (the wheel's whole perf claim is that
# these are interchangeable; exactness says they must be IDENTICAL)
# ----------------------------------------------------------------------

def _assert_wheel_equal(a: FP.WheelIndex, b: FP.WheelIndex):
    """Index equality modulo the observability counters (reslots/hwm
    deliberately differ: adjust counts movement, build starts
    fresh)."""
    for f in ("origin", "cnt", "bmin", "slot", "key"):
        assert bool(jnp.array_equal(getattr(a, f), getattr(b, f))), \
            f"wheel field {f} diverged from rebuild"


def test_adjust_equals_rebuild_served_commit():
    """Fixed-now commit: re-slotting exactly the served clients
    reproduces the full rebuild of the committed state."""
    state = zipf64_state(n=10, depth=32)
    now = jnp.int64(500 * S)
    w = FP.wheel_build(state, now, False)
    b = wheel_batch(state, 500 * S, 8, 2)
    assert int(b.count) > 0
    moved = b.served > 0
    adj = FP.wheel_adjust(w, b.state, now, False, moved)
    _assert_wheel_equal(adj, FP.wheel_build(b.state, now, False))
    assert int(adj.reslots) > 0
    assert int(adj.hwm) >= int(w.hwm)


def test_adjust_equals_rebuild_live_qos_update():
    """A live PUT /clients/{id}/qos rewrites one client's rate
    params and head tags at the boundary; adjusting that client alone
    must equal the rebuild."""
    state = zipf64_state(n=10, depth=32)
    now = jnp.int64(500 * S)
    w = FP.wheel_build(state, now, False)
    c = 3
    onehot = jnp.arange(state.capacity) == c
    new_state = state._replace(
        weight_inv=state.weight_inv.at[c].set(
            state.weight_inv[c] // 4),
        head_prop=state.head_prop.at[c].set(
            state.head_prop[c] // 2))
    adj = FP.wheel_adjust(w, new_state, now, False, onehot)
    _assert_wheel_equal(adj, FP.wheel_build(new_state, now, False))


def test_adjust_equals_rebuild_idle_reentry():
    """A client departing (CLS_NONE, unslotted) and re-entering must
    round-trip through the adjust in both directions."""
    state = zipf64_state(n=10, depth=32)
    now = jnp.int64(500 * S)
    c = 5
    onehot = jnp.arange(state.capacity) == c
    idle = state._replace(active=state.active.at[c].set(False))
    w = FP.wheel_build(state, now, False)
    adj_out = FP.wheel_adjust(w, idle, now, False, onehot)
    _assert_wheel_equal(adj_out, FP.wheel_build(idle, now, False))
    # unslotted rows park at 3B
    assert int(adj_out.slot[c]) == 3 * FP._WHEEL_BUCKETS
    # ... and back in
    adj_in = FP.wheel_adjust(adj_out, state, now, False, onehot)
    _assert_wheel_equal(adj_in, w)


def test_adjust_equals_rebuild_churn_boundary_reslot():
    """Churn boundary at fixed now: one slot evicted and recycled
    for a fresh registration with different QoS/tags; adjusting the
    recycled slot equals the rebuild."""
    state = zipf64_state(n=10, depth=32)
    now = jnp.int64(500 * S)
    w = FP.wheel_build(state, now, False)
    c = 7
    onehot = jnp.arange(state.capacity) == c
    evicted = state._replace(
        active=state.active.at[c].set(False),
        depth=state.depth.at[c].set(0))
    adj = FP.wheel_adjust(w, evicted, now, False, onehot)
    _assert_wheel_equal(adj, FP.wheel_build(evicted, now, False))
    recycled = evicted._replace(
        active=evicted.active.at[c].set(True),
        depth=state.depth.at[c].set(2),
        weight_inv=evicted.weight_inv.at[c].set(
            evicted.weight_inv[c] * 3),
        head_prop=evicted.head_prop.at[c].set(
            jnp.int64(now + 1_000_000)))
    adj2 = FP.wheel_adjust(adj, recycled, now, False, onehot)
    _assert_wheel_equal(adj2, FP.wheel_build(recycled, now, False))


# ----------------------------------------------------------------------
# the exactness identity: first occupied bucket's min == dense min
# ----------------------------------------------------------------------

def test_wheel_origins_match_dense_min():
    for state, now in ((zipf64_state(n=12, depth=16), 500 * S),
                       mixed_qos_state(n=8, depth=10)):
        now = jnp.int64(now)
        for allow in (False, True):
            w = FP.wheel_build(state, now, allow)
            kresv, kprop1, kprop2, any_c = FP.wheel_origins(w)
            cls, key = FP._classify(state, now, allow)
            for c, got in ((FP.CLS_RESV, kresv),
                           (FP.CLS_WEIGHT, kprop1),
                           (FP.CLS_LB, kprop2)):
                want = jnp.min(jnp.where(cls == c, key, FP.KEY_INF))
                assert int(got) == int(want), (allow, int(c))
            assert bool(any_c) == bool((cls != FP.CLS_NONE).any())


def test_wheel_stop_min_matches_dense_min():
    rng = np.random.default_rng(23)
    for _ in range(4):
        stops = rng.integers(0, 1 << 60, size=64, dtype=np.int64)
        inf_mask = rng.random(64) < 0.3
        stops = np.where(inf_mask, kernels.KEY_INF, stops)
        got = FP._wheel_stop_min(jnp.asarray(stops),
                                 kernels.wheel_scan)
        assert int(got) == int(stops.min())
    # all-INF distributions return KEY_INF like the dense min
    all_inf = jnp.full((16,), jnp.int64(kernels.KEY_INF))
    assert int(FP._wheel_stop_min(all_inf, kernels.wheel_scan)) \
        == kernels.KEY_INF


# ----------------------------------------------------------------------
# Pallas kernel parity; a pallas request that cannot run raises
# ----------------------------------------------------------------------

def test_pallas_interpret_bit_identical(monkeypatch):
    """DMCLOCK_WHEEL_INTERPRET=1 resolves wheel_kernel="pallas" to
    the interpret-mode Pallas kernel; the batch must be bit-identical
    to the XLA kernel -- the ci.sh parity pin."""
    monkeypatch.setenv("DMCLOCK_WHEEL_INTERPRET", "1")
    assert FP._wheel_resolve("pallas", 16) is not kernels.wheel_scan
    state = zipf64_state(n=10, depth=16)
    bx = FP.calendar_batch_wheel(state, jnp.int64(500 * S), steps=6,
                                 levels=2, wheel_kernel="xla")
    bp = FP.calendar_batch_wheel(state, jnp.int64(500 * S), steps=6,
                                 levels=2, wheel_kernel="pallas")
    assert_batches_equal(bx, bp)
    assert int(bx.count) > 0


def test_pallas_unsupported_shape_raises(monkeypatch):
    monkeypatch.setenv("DMCLOCK_WHEEL_INTERPRET", "1")
    # > 2^19 padded lanes: past the gridless kernel's budget
    with pytest.raises(ValueError, match="does not support n="):
        FP._wheel_resolve("pallas", 1 << 20)
    with pytest.raises(ValueError, match="wheel_kernel"):
        FP._wheel_resolve("mosaic", 16)


def test_pallas_off_tpu_raises(monkeypatch):
    """Off TPU without interpret mode a pallas request raises -- at
    resolution and through the epoch scan -- instead of quietly
    running the XLA reference; the xla kernel still runs."""
    monkeypatch.delenv("DMCLOCK_WHEEL_INTERPRET", raising=False)
    state = zipf64_state(n=8, depth=16)
    now = jnp.int64(500 * S)
    kw = dict(steps=6, anticipation_ns=0, calendar_impl="wheel",
              ladder_levels=2, with_metrics=True)
    with pytest.raises(ValueError, match="needs a TPU"):
        FP._wheel_resolve("pallas", 16)
    with pytest.raises(ValueError, match="needs a TPU"):
        FP.scan_calendar_epoch(state, now, 2, wheel_kernel="pallas",
                               **kw)
    ex = FP.scan_calendar_epoch(state, now, 2, wheel_kernel="xla",
                                **kw)
    assert int(np.asarray(ex.count).sum()) > 0


# ----------------------------------------------------------------------
# epoch plumbing: scan_calendar_epoch(calendar_impl="wheel")
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_wheel_epoch_matches_batches():
    state, now = mixed_qos_state(n=8, depth=10)
    m, steps, levels = 4, 6, 2
    ep = FP.scan_calendar_epoch(state, jnp.int64(now), m,
                                steps=steps, anticipation_ns=0,
                                calendar_impl="wheel",
                                ladder_levels=levels)
    st = state
    total_served = np.zeros(state.capacity, np.int32)
    for i in range(m):
        b = wheel_batch(st, now, steps, levels)
        assert int(b.count) == int(jax.device_get(ep.count)[i])
        total_served += jax.device_get(b.served)
        st = b.state
    assert np.array_equal(total_served, jax.device_get(ep.served))
    assert_states_equal(ep.state, st)


def test_wheel_epoch_metrics():
    """with_metrics invisible to the wheel decision stream; the three
    new rows account the index's work: occupancy HWM > 0 on any
    non-empty build, re-slots > 0 once commits move clients."""
    from dmclock_tpu.obs import device as obsdev

    state = zipf64_state(n=8, depth=16)
    now = jnp.int64(500 * S)
    kw = dict(steps=6, anticipation_ns=0, calendar_impl="wheel",
              ladder_levels=3)
    ep_off = FP.scan_calendar_epoch(state, now, 2, **kw)
    ep_on = FP.scan_calendar_epoch(state, now, 2, with_metrics=True,
                                   **kw)
    for f in ("count", "resv_count", "progress_ok", "served",
              "level_count"):
        assert bool(jnp.array_equal(getattr(ep_off, f),
                                    getattr(ep_on, f))), \
            f"wheel epoch field {f} diverged with metrics on"
    assert_states_equal(ep_off.state, ep_on.state)
    m = obsdev.metrics_dict(ep_on.metrics)
    assert m["decisions_total"] == \
        int(np.asarray(ep_on.level_count).sum())
    assert m["wheel_bucket_occupancy_hwm"] > 0
    assert m["wheel_reslots_total"] > 0
    assert m["wheel_pallas_fallbacks"] == 0
    assert m["calendar_ladder_fallbacks"] == 0


@pytest.mark.slow
def test_wheel_epoch_tag32_exact():
    """The int32 tag carry composes with the wheel exactly as with
    the bucketed path (window-fitting high-rate shape)."""
    infos = {c: ClientInfo(0, 1000.0 + 500 * (c % 3), 0)
             for c in range(6)}
    state = deep_state(infos, depth=12)
    kw = dict(steps=4, anticipation_ns=0, calendar_impl="wheel",
              ladder_levels=2)
    now = jnp.int64(2 * S)
    e64 = FP.scan_calendar_epoch(state, now, 2, tag_width=64, **kw)
    e32 = FP.scan_calendar_epoch(state, now, 2, tag_width=32, **kw)
    assert bool(jax.device_get(e32.progress_ok).all())
    for f in ("count", "resv_count", "progress_ok", "served",
              "level_count"):
        assert bool(jnp.array_equal(getattr(e64, f),
                                    getattr(e32, f))), f
    assert_states_equal(e64.state, e32.state)


# ----------------------------------------------------------------------
# live PUT mid-epoch-stream: the lifecycle plane drives the wheel
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_wheel_churn_stream_equals_bucketed():
    """Scripted QoS updates (limit_thrash's PUT /clients/{id}/qos
    script) applied at boundaries MID-STREAM, plus registrations and
    evictions (churn_storm), must leave wheel == bucketed digests on
    the streaming loop -- the lifecycle plane's state rewrites hit
    the wheel's rebuild/adjust paths, not just steady serving."""
    import dataclasses

    from dmclock_tpu.lifecycle import make_spec
    from dmclock_tpu.robust import supervisor as SV

    for spec in (make_spec("limit_thrash", total_ids=12,
                           base_lam=1.5),
                 make_spec("churn_storm", total_ids=16, base_lam=1.5,
                           compact_every=1, gens=4, stride=4, life=2,
                           capacity0=4)):
        base = SV.EpochJob(engine="calendar", churn=spec, epochs=12,
                           m=2, k=8, ring=16, waves=4, ckpt_every=2,
                           seed=11, engine_loop="stream",
                           calendar_impl="wheel", ladder_levels=2)
        w = SV.run_job(base)
        b = SV.run_job(dataclasses.replace(
            base, calendar_impl="bucketed"))
        assert w.decisions == b.decisions > 0, spec["scenario"]
        assert w.digest == b.digest, spec["scenario"]
        assert w.state_digest == b.state_digest, spec["scenario"]
        if spec["scenario"] == "limit_thrash":
            assert w.lifecycle["qos_updates"] > 0
