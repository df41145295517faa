"""Device telemetry plane tests (obs.histograms / obs.flight).

The load-bearing contracts:

1. **On/off bit-identity** -- enabling any combination of histograms,
   ledger, and flight recorder must not perturb the decision stream or
   the final engine state, on all three epoch engines and the
   radix/tag32/bucketed fast paths (the telemetry is pure reductions
   over arrays the kernels already materialize).
2. **Cross-impl exactness** -- the telemetry CONTENTS are equal across
   fast paths that commit identical decision streams: sort == radix,
   tag32 == int64 (window holding), bucketed L=1 == minstop bitwise,
   and bucketed-L == the composition of L minstop batches (a ladder
   level IS one minstop batch).
3. **Device truth** -- the per-client ledger equals a host-side
   recomputation from the emitted decision streams (prefix) and the
   calendar served vectors (seeded cfg4-flavored run).
4. **Flight ring** -- wraparound keeps exactly the newest R records
   with a monotone seq, deterministically, including the
   one-batch-overflow case.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.core import ClientInfo, NS_PER_SEC
from dmclock_tpu.core.timebase import rate_to_inv_ns
from dmclock_tpu.engine.fastpath import (scan_calendar_epoch,
                                         scan_chain_epoch,
                                         scan_prefix_epoch)
from dmclock_tpu.obs import MetricsRegistry
from dmclock_tpu.obs import device as obsdev
from dmclock_tpu.obs import flight as obsflight
from dmclock_tpu.obs import histograms as obshist
from dmclock_tpu.robust.guarded import run_epoch_guarded

from engine_helpers import assert_states_equal, deep_state

S = NS_PER_SEC

INFOS = {
    0: ClientInfo(10.0, 2.0, 50.0),
    1: ClientInfo(5.0, 1.0, 40.0),
    2: ClientInfo(0.0, 3.0, 0.0),
}


def _mixed_state(depth=6):
    return deep_state(INFOS, depth)


def _kit(n, records=64):
    return dict(hists=obshist.hist_zero(),
                ledger=obshist.ledger_zero(n),
                flight=obsflight.flight_init(records))


# ----------------------------------------------------------------------
# bucket math
# ----------------------------------------------------------------------

class TestBucketing:
    def test_bucket_index_exact(self):
        v = jnp.asarray([-7, 0, 1, 2, 3, 4, 7, 8,
                         (1 << 46) - 1, 1 << 46, 1 << 60])
        idx = jax.device_get(obshist.bucket_index(v)).tolist()
        assert idx == [0, 0, 1, 2, 2, 3, 3, 4, 46, 47, 47]

    def test_observe_counts_and_sum(self):
        h = obshist.hist_zero()
        vals = jnp.asarray([0, 1, 5, 1000, -3], dtype=jnp.int64)
        mask = jnp.asarray([True, True, True, True, False])
        h = obshist.hist_observe(h, obshist.HIST_RESV_TARDINESS,
                                 vals, mask)
        d = obshist.hist_dict(h)["resv_tardiness_ns"]
        assert d["count"] == 4
        assert d["sum"] == 0 + 1 + 5 + 1000
        assert d["buckets"][0] == 1          # the 0
        assert d["buckets"][1] == 1          # the 1
        assert d["buckets"][3] == 1          # 5 in [4, 8)
        assert d["buckets"][10] == 1         # 1000 in [512, 1024)

    def test_observe_scalar_weight_zero(self):
        h = obshist.hist_zero()
        h = obshist.hist_observe_scalar(h, obshist.HIST_LIMIT_STALL,
                                        12345, 0)
        assert obshist.hist_dict(h)["limit_stall_ns"]["count"] == 0
        h = obshist.hist_observe_scalar(h, obshist.HIST_LIMIT_STALL,
                                        12345, 1)
        d = obshist.hist_dict(h)["limit_stall_ns"]
        assert d["count"] == 1 and d["sum"] == 12345

    def test_percentile_upper_bounds(self):
        h = np.zeros((obshist.NUM_HISTS, obshist.NUM_BUCKETS + 1),
                     dtype=np.int64)
        assert obshist.hist_percentile(h, 0, 0.99) == 0.0
        # 90 values in bucket 1 (v=1), 10 in bucket 10 (~1000)
        h[0, 1] = 90
        h[0, 10] = 10
        assert obshist.hist_percentile(h, 0, 0.50) == 1.0
        assert obshist.hist_percentile(h, 0, 0.99) == float(2**10 - 1)

    def test_combine_and_mirrors(self):
        a = obshist.hist_zero().at[0, 3].add(5).at[1, 48].add(100)
        b = obshist.hist_zero().at[0, 3].add(2)
        c = jax.device_get(obshist.hist_combine(a, b))
        assert c[0, 3] == 7 and c[1, 48] == 100
        la = obshist.ledger_zero(3).at[0].set(
            jnp.asarray([3, 1, 0, 50, 30], dtype=jnp.int64))
        lb = obshist.ledger_zero(3).at[0].set(
            jnp.asarray([2, 2, 1, 20, 40], dtype=jnp.int64))
        dev = jax.device_get(obshist.ledger_combine(la, lb))
        host = obshist.ledger_combine_np(jax.device_get(la),
                                         jax.device_get(lb))
        assert np.array_equal(dev, host)
        assert dev[0].tolist() == [5, 3, 1, 70, 40]  # max col maxes


# ----------------------------------------------------------------------
# on/off bit-identity across engines and fast paths
# ----------------------------------------------------------------------

ENGINE_RUNS = {
    "prefix-sort": lambda st, now, **tele: scan_prefix_epoch(
        st, now, 3, 4, anticipation_ns=0, with_metrics=True, **tele),
    "prefix-radix": lambda st, now, **tele: scan_prefix_epoch(
        st, now, 3, 4, anticipation_ns=0, select_impl="radix", **tele),
    "prefix-tag32": lambda st, now, **tele: scan_prefix_epoch(
        st, now, 3, 4, anticipation_ns=0, tag_width=32, **tele),
    "prefix-window": lambda st, now, **tele: scan_prefix_epoch(
        st, now, 4, 4, anticipation_ns=0, window_m=2, **tele),
    "chain": lambda st, now, **tele: scan_chain_epoch(
        st, now, 2, 4, chain_depth=3, anticipation_ns=0,
        use_pallas=False, with_metrics=True, **tele),
    "calendar-minstop": lambda st, now, **tele: scan_calendar_epoch(
        st, now, 2, steps=4, use_pallas=False, with_metrics=True,
        **tele),
    "calendar-bucketed": lambda st, now, **tele: scan_calendar_epoch(
        st, now, 2, steps=4, use_pallas=False,
        calendar_impl="bucketed", ladder_levels=2, **tele),
    "calendar-tag32": lambda st, now, **tele: scan_calendar_epoch(
        st, now, 2, steps=4, use_pallas=False, tag_width=32, **tele),
}

_DEC_FIELDS = {
    "prefix": ("count", "guards_ok", "slot", "phase", "cost", "lb"),
    "chain": ("count", "unit_count", "guards_ok", "slot", "cls",
              "length"),
    "calendar": ("count", "resv_count", "progress_ok", "served",
                 "level_count"),
}


class TestOnOffBitIdentity:
    # heavy fast-path cells are slow-marked for the tier-1 wall
    # budget (scripts/run_tests.sh runs the full matrix; the ci.sh
    # telemetry smoke gates prefix + bucketed-calendar cheaply)
    @pytest.mark.parametrize("name", [
        "prefix-sort", "prefix-tag32", "prefix-window", "chain",
        pytest.param("prefix-radix", marks=pytest.mark.slow),
        pytest.param("calendar-minstop", marks=pytest.mark.slow),
        pytest.param("calendar-bucketed", marks=pytest.mark.slow),
        pytest.param("calendar-tag32", marks=pytest.mark.slow),
    ])
    def test_decisions_identical_with_telemetry(self, name):
        run = ENGINE_RUNS[name]
        now = jnp.int64(1 * S)
        ep_off = run(_mixed_state(), now)
        ep_on = run(_mixed_state(), now, **_kit(64))
        fields = _DEC_FIELDS[name.split("-")[0]]
        for f in fields:
            assert bool(jnp.array_equal(getattr(ep_off, f),
                                        getattr(ep_on, f))), \
                f"{name}: field {f} diverged with telemetry on"
        assert_states_equal(ep_off.state, ep_on.state)
        assert bool(jnp.array_equal(ep_off.metrics, ep_on.metrics))
        # off = absent, not zeros
        assert ep_off.hists is None and ep_off.ledger is None \
            and ep_off.flight is None

    @pytest.mark.parametrize("name", [
        "prefix-sort", "chain", "calendar-minstop",
        pytest.param("prefix-radix", marks=pytest.mark.slow),
        pytest.param("prefix-tag32", marks=pytest.mark.slow),
        pytest.param("prefix-window", marks=pytest.mark.slow),
        pytest.param("calendar-bucketed", marks=pytest.mark.slow),
        pytest.param("calendar-tag32", marks=pytest.mark.slow),
    ])
    def test_ledger_totals_match_stream(self, name):
        run = ENGINE_RUNS[name]
        ep = run(_mixed_state(), jnp.int64(1 * S), **_kit(64))
        led = np.asarray(jax.device_get(ep.ledger))
        total = int(np.asarray(jax.device_get(ep.count)).sum())
        assert led[:, obshist.LED_OPS].sum() == total
        d = obshist.hist_dict(ep.hists)
        # every committed entry head observed exactly once, in exactly
        # one of the two latency families; at chain_depth=1 every
        # decision IS an entry head, so the counts cover the stream
        if name.startswith("prefix"):
            assert d["decision_latency_ns"]["count"] \
                + d["resv_tardiness_ns"]["count"] == total
        # commit-size sum over batches/levels == total decisions
        assert d["commit_size"]["sum"] == total
        # flight seq advanced iff work committed (calendar-tag32
        # legitimately trips its window on this fixture and commits 0;
        # a gated batch must record nothing)
        assert (int(jax.device_get(ep.flight.seq)) > 0) == (total > 0)


class TestCrossImplEquality:
    def _tele(self, ep):
        return (np.asarray(jax.device_get(ep.hists)),
                np.asarray(jax.device_get(ep.ledger)))

    @pytest.mark.slow
    def test_sort_vs_radix(self):
        now = jnp.int64(1 * S)
        eps = [scan_prefix_epoch(_mixed_state(), now, 3, 4,
                                 anticipation_ns=0, select_impl=impl,
                                 hists=obshist.hist_zero(),
                                 ledger=obshist.ledger_zero(64))
               for impl in ("sort", "radix")]
        ha, la = self._tele(eps[0])
        hb, lb = self._tele(eps[1])
        assert np.array_equal(ha, hb)
        assert np.array_equal(la, lb)

    @pytest.mark.slow
    def test_tag32_vs_int64(self):
        # high-rate QoS (~1e6 ns/serve tag advance): the whole epoch
        # stays inside the +-2^31 ns window (the test_radix fixture)
        infos = {c: ClientInfo(2000, 1000 * (1 + c % 3), 0)
                 for c in range(12)}
        now = jnp.int64(4 * S)
        eps = [scan_prefix_epoch(deep_state(infos, 6), now, 3, 4,
                                 anticipation_ns=0, tag_width=w,
                                 hists=obshist.hist_zero(),
                                 ledger=obshist.ledger_zero(64))
               for w in (64, 32)]
        assert bool(jax.device_get(eps[1].guards_ok).all())
        ha, la = self._tele(eps[0])
        hb, lb = self._tele(eps[1])
        assert np.array_equal(ha, hb)
        assert np.array_equal(la, lb)

    def test_bucketed_l1_bitwise_minstop(self):
        now = jnp.int64(1 * S)
        kw = dict(steps=4, use_pallas=False,
                  hists=obshist.hist_zero(),
                  ledger=obshist.ledger_zero(64))
        a = scan_calendar_epoch(_mixed_state(), now, 2,
                                calendar_impl="minstop", **kw)
        b = scan_calendar_epoch(_mixed_state(), now, 2,
                                calendar_impl="bucketed",
                                ladder_levels=1, **kw)
        ha, la = self._tele(a)
        hb, lb = self._tele(b)
        assert np.array_equal(ha, hb)
        assert np.array_equal(la, lb)

    def test_bucketed_equals_minstop_composition(self):
        """m=1 bucketed epoch at L levels == m=L minstop epoch: each
        ladder level starts from the exact serial state one minstop
        batch would leave, so the per-level telemetry observations
        compose identically."""
        now = jnp.int64(1 * S)
        kw = dict(steps=4, use_pallas=False)
        a = scan_calendar_epoch(_mixed_state(), now, 3,
                                calendar_impl="minstop",
                                hists=obshist.hist_zero(),
                                ledger=obshist.ledger_zero(64), **kw)
        b = scan_calendar_epoch(_mixed_state(), now, 1,
                                calendar_impl="bucketed",
                                ladder_levels=3,
                                hists=obshist.hist_zero(),
                                ledger=obshist.ledger_zero(64), **kw)
        assert int(jax.device_get(a.count).sum()) \
            == int(jax.device_get(b.count).sum())
        ha, la = self._tele(a)
        hb, lb = self._tele(b)
        assert np.array_equal(ha, hb)
        assert np.array_equal(la, lb)
        assert_states_equal(a.state, b.state)


# ----------------------------------------------------------------------
# ledger == host recomputation (device truth)
# ----------------------------------------------------------------------

def _zipf_cfg4_state(n=512, ring=16, depth=8):
    """cfg4-flavored seeded population: Zipf weights + uniform
    reservations, both phases active (the bench workload in
    miniature)."""
    from __graft_entry__ import _preloaded_state

    st = _preloaded_state(n, depth, ring=ring)
    w = np.clip(1.0 / np.arange(1, n + 1) ** 1.1
                / (1.0 / (n // 2) ** 1.1), 0.5, 64.0)
    rng = np.random.default_rng(7)
    rng.shuffle(w)
    winv = np.asarray([rate_to_inv_ns(x) for x in w], np.int64)
    # reservation floor sized so the constraint phase takes PART of
    # service over the test's ~3e8 ns window (rate 10/s -> ~3 of the
    # 8-deep backlog per client), leaving real weight-phase serves
    rinv = np.full(n, rate_to_inv_ns(10.0), dtype=np.int64)
    return st._replace(weight_inv=jnp.asarray(winv),
                       head_prop=jnp.asarray(winv),
                       resv_inv=jnp.asarray(rinv),
                       head_resv=jnp.asarray(rinv))


class TestLedgerDeviceTruth:
    def test_prefix_ledger_equals_host_recount(self):
        """The full decision stream (slot/phase/lb per batch) is the
        host-side ground truth; the ledger must reproduce it
        exactly."""
        st = _mixed_state(depth=8)
        ep = scan_prefix_epoch(st, jnp.int64(1 * S), 4, 4,
                               anticipation_ns=0,
                               allow_limit_break=True,
                               ledger=obshist.ledger_zero(64))
        led = np.asarray(jax.device_get(ep.ledger))
        slot = np.asarray(jax.device_get(ep.slot)).ravel()
        phase = np.asarray(jax.device_get(ep.phase)).ravel()
        lb = np.asarray(jax.device_get(ep.lb)).ravel()
        ops = np.zeros(64, dtype=np.int64)
        resv = np.zeros(64, dtype=np.int64)
        lbs = np.zeros(64, dtype=np.int64)
        ok = slot >= 0
        np.add.at(ops, slot[ok], 1)
        np.add.at(resv, slot[ok & (phase == 0)], 1)
        np.add.at(lbs, slot[ok & lb], 1)
        assert np.array_equal(led[:, obshist.LED_OPS], ops)
        assert np.array_equal(led[:, obshist.LED_RESV_OPS], resv)
        assert np.array_equal(led[:, obshist.LED_LIMIT_BREAKS], lbs)

    def test_cfg4_calendar_ledger_equals_served_accumulation(self):
        """Seeded cfg4-flavored run, accumulators chained across
        epochs on device: the ledger's ops column == the host-summed
        per-epoch served vectors, and the phase totals match the
        metrics vector."""
        st = _zipf_cfg4_state()
        hists = obshist.hist_zero()
        ledger = obshist.ledger_zero(512)
        served_host = np.zeros(512, dtype=np.int64)
        resv_total = 0
        now = 0
        run = jax.jit(functools.partial(
            scan_calendar_epoch, m=2, steps=6, use_pallas=False,
            with_metrics=True, calendar_impl="bucketed",
            ladder_levels=2))
        met = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
        for _ in range(3):
            now += 10 ** 8
            ep = run(st, jnp.int64(now), hists=hists, ledger=ledger)
            st, hists, ledger = ep.state, ep.hists, ep.ledger
            served_host += np.asarray(jax.device_get(ep.served))
            resv_total += int(jax.device_get(ep.resv_count).sum())
            met = obsdev.metrics_combine_np(
                met, jax.device_get(ep.metrics))
        led = np.asarray(jax.device_get(ledger))
        assert np.array_equal(led[:, obshist.LED_OPS], served_host)
        assert led[:, obshist.LED_RESV_OPS].sum() == resv_total
        assert led[:, obshist.LED_OPS].sum() \
            == met[obsdev.MET_DECISIONS]
        assert led[:, obshist.LED_RESV_OPS].sum() \
            == met[obsdev.MET_RESV]
        # both phases genuinely active in the fixture
        assert 0 < resv_total < int(served_host.sum())
        # tardiness columns populated and self-consistent
        assert (led[:, obshist.LED_TARD_MAX]
                <= led[:, obshist.LED_TARD_SUM]).all()


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------

class TestFlightRecorder:
    def test_wraparound_keeps_newest(self):
        fl = obsflight.flight_init(8)
        for b in range(4):
            slot = jnp.asarray([b * 3, b * 3 + 1, b * 3 + 2],
                               dtype=jnp.int32)
            fl = obsflight.flight_record(
                fl, slot, jnp.zeros(3, jnp.int64),
                jnp.full(3, b, jnp.int64), jnp.ones(3, jnp.int64))
        assert int(jax.device_get(fl.seq)) == 12
        assert int(jax.device_get(fl.batch)) == 4
        recs = obsflight.flight_drain(fl)
        assert len(recs) == 8
        assert [r["seq"] for r in recs] == list(range(4, 12))
        assert recs[-1]["client"] == 11 and recs[-1]["batch"] == 3

    def test_one_batch_overflow_deterministic(self):
        fl = obsflight.flight_init(4)
        slot = jnp.arange(10, dtype=jnp.int32)
        fl = obsflight.flight_record(
            fl, slot, jnp.zeros(10, jnp.int64),
            jnp.arange(10, dtype=jnp.int64) * 7,
            jnp.ones(10, jnp.int64))
        assert int(jax.device_get(fl.seq)) == 10
        recs = obsflight.flight_drain(fl)
        assert [r["seq"] for r in recs] == [6, 7, 8, 9]
        assert [r["client"] for r in recs] == [6, 7, 8, 9]

    def test_masked_and_dead_batches_write_nothing(self):
        fl = obsflight.flight_init(8)
        none = jnp.full(4, -1, dtype=jnp.int32)
        z = jnp.zeros(4, jnp.int64)
        fl = obsflight.flight_record(fl, none, z, z, z)
        assert int(jax.device_get(fl.seq)) == 0
        assert int(jax.device_get(fl.batch)) == 1  # live, 0 records
        fl = obsflight.flight_record(
            fl, jnp.arange(4, dtype=jnp.int32), z, z, z,
            live=jnp.bool_(False))
        assert int(jax.device_get(fl.seq)) == 0    # dead: gated out
        assert int(jax.device_get(fl.batch)) == 1
        assert obsflight.flight_drain(fl) == []

    def test_scattered_mask_ranks(self):
        fl = obsflight.flight_init(8)
        slot = jnp.asarray([-1, 5, -1, 9, -1, 2], dtype=jnp.int32)
        fl = obsflight.flight_record(
            fl, slot, jnp.zeros(6, jnp.int64),
            jnp.zeros(6, jnp.int64), jnp.ones(6, jnp.int64))
        recs = obsflight.flight_drain(fl)
        assert [r["client"] for r in recs] == [5, 9, 2]
        assert [r["seq"] for r in recs] == [0, 1, 2]

    def test_dump_round_trip(self, tmp_path):
        fl = obsflight.flight_init(4)
        fl = obsflight.flight_record(
            fl, jnp.asarray([1, 2], jnp.int32),
            jnp.asarray([0, 1], jnp.int64),
            jnp.asarray([10, 20], jnp.int64),
            jnp.asarray([1, 3], jnp.int64))
        p = tmp_path / "flight.jsonl"
        n = obsflight.flight_dump(fl, str(p))
        rows = [json.loads(l) for l in p.read_text().splitlines()]
        assert n == len(rows) == 2
        assert rows[1] == {"seq": 1, "batch": 0, "client": 2,
                           "cls": 1, "tag": 20, "cost": 3,
                           "margin": -1, "gate": 0}

    def test_epoch_flight_matches_stream(self):
        """Prefix-epoch flight records ARE the decision stream's tail
        (client/cost per committed decision, in commit order)."""
        ep = scan_prefix_epoch(_mixed_state(), jnp.int64(1 * S), 3, 4,
                               anticipation_ns=0,
                               flight=obsflight.flight_init(256))
        slot = np.asarray(jax.device_get(ep.slot)).ravel()
        cost = np.asarray(jax.device_get(ep.cost)).ravel()
        ok = slot >= 0
        recs = obsflight.flight_drain(ep.flight)
        assert [r["client"] for r in recs] == slot[ok].tolist()
        assert [r["cost"] for r in recs] == cost[ok].tolist()
        assert int(jax.device_get(ep.flight.seq)) == int(ok.sum())


# ----------------------------------------------------------------------
# mesh merge (the psum/pmax collective path)
# ----------------------------------------------------------------------

class TestMeshReduce:
    def test_pmax_i64_is_the_int64_max(self):
        """The two-int32-pass max (TPU lowers only SUM for int64
        all-reduces) equals the int64 max over the shards, across
        sign, high-word ties, and the low word's top bit."""
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 (virtual) devices")
        from jax.sharding import Mesh, PartitionSpec as P

        from dmclock_tpu.obs import device as obsdev

        mesh = Mesh(np.array(jax.devices()[:4]), ("servers",))
        x = np.array([
            [-1, 5, (1 << 40) + 7, -(1 << 62), (1 << 32) + 0x80000000],
            [-2, 5, (1 << 40) + 0xFFFFFFFF, -(1 << 62) + 1, 1 << 32],
            [-(1 << 63), 4, 1 << 40, -(1 << 63), (1 << 32) + 1],
            [-3, -5, (1 << 40) + 0x80000000, -5, 0x7FFFFFFF],
        ], dtype=np.int64)
        got = jax.shard_map(
            lambda v: obsdev.pmax_i64(v[0], "servers"), mesh=mesh,
            in_specs=P("servers"), out_specs=P(),
            check_vma=False)(jnp.asarray(x))
        assert np.array_equal(np.asarray(got), x.max(axis=0))

    def test_hist_and_ledger_mesh_reduce(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 (virtual) devices")
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]), ("servers",))
        hs = jnp.stack([obshist.hist_zero().at[0, i].add(i + 1)
                        for i in range(4)])
        ls = jnp.stack([
            obshist.ledger_zero(5).at[0].set(jnp.asarray(
                [i, 0, 0, 10 * i, 10 * i], dtype=jnp.int64))
            for i in range(4)])

        def merge(h, l):
            return (obshist.hist_mesh_reduce(h[0], "servers"),
                    obshist.ledger_mesh_reduce(l[0], "servers"))

        mh, ml = jax.shard_map(
            merge, mesh=mesh,
            in_specs=(P("servers"), P("servers")),
            out_specs=(P(), P()))(hs, ls)
        want_h = np.asarray(jax.device_get(hs)).sum(axis=0)
        assert np.array_equal(np.asarray(jax.device_get(mh)), want_h)
        ml = np.asarray(jax.device_get(ml))
        assert ml[0, obshist.LED_OPS] == 0 + 1 + 2 + 3
        assert ml[0, obshist.LED_TARD_SUM] == 60      # psum
        assert ml[0, obshist.LED_TARD_MAX] == 30      # pmax


# ----------------------------------------------------------------------
# guarded runner pass-through
# ----------------------------------------------------------------------

class TestGuardedTelemetry:
    def test_guarded_matches_bare_epoch(self):
        st = _mixed_state()
        now = 1 * S
        bare = scan_prefix_epoch(st, jnp.int64(now), 3, 4,
                                 anticipation_ns=0,
                                 hists=obshist.hist_zero(),
                                 ledger=obshist.ledger_zero(64),
                                 flight=obsflight.flight_init(32))
        ep = run_epoch_guarded(st, now, engine="prefix", m=3, k=4,
                               hists=obshist.hist_zero(),
                               ledger=obshist.ledger_zero(64),
                               flight=obsflight.flight_init(32))
        assert np.array_equal(np.asarray(jax.device_get(bare.hists)),
                              np.asarray(jax.device_get(ep.hists)))
        assert np.array_equal(np.asarray(jax.device_get(bare.ledger)),
                              np.asarray(jax.device_get(ep.ledger)))
        assert np.array_equal(
            np.asarray(jax.device_get(bare.flight.buf)),
            np.asarray(jax.device_get(ep.flight.buf)))

    def test_tag32_window_trip_resume_accumulates(self):
        """A deterministic tag32 window trip: the int64 resume must
        CONTINUE the accumulators, so the final ledger still equals
        the guarded run's total committed count."""
        st = _mixed_state()
        st = st._replace(head_prop=st.head_prop.at[0]
                         .add(jnp.int64(1) << 40))
        ep = run_epoch_guarded(st, 1 * S, engine="prefix", m=3, k=4,
                               tag_width=32,
                               ledger=obshist.ledger_zero(64))
        assert ep.rebase_fallbacks == 1
        led = np.asarray(jax.device_get(ep.ledger))
        assert led[:, obshist.LED_OPS].sum() == ep.count


# ----------------------------------------------------------------------
# queue host-ledger mirror
# ----------------------------------------------------------------------

class TestQueueLedger:
    def test_pull_queue_ledger_matches_counters(self):
        from dmclock_tpu.core.recs import ReqParams
        from dmclock_tpu.engine import TpuPullPriorityQueue

        q = TpuPullPriorityQueue(lambda c: INFOS[c], capacity=8,
                                 ring_capacity=8)
        t = 1 * S
        for i in range(6):
            q.add_request(("r", i), i % 2, ReqParams(1, 1),
                          time_ns=t, cost=1)
        served = 0
        for _ in range(6):
            pr = q.pull_request(now_ns=t + served * 10)
            if pr.is_retn():
                served += 1
        rows = q.ledger_rows()
        assert sum(int(r[0]) for r in rows.values()) == served \
            == q.reserv_sched_count + q.prop_sched_count
        assert sum(int(r[1]) for r in rows.values()) \
            == q.reserv_sched_count
        # tardiness columns stay zero on the host mirror (documented)
        assert all(int(r[3]) == 0 and int(r[4]) == 0
                   for r in rows.values())

    @pytest.mark.slow
    def test_sim_ledger_check_cross_checks(self):
        from dmclock_tpu.sim import ClientGroup, ServerGroup, SimConfig
        from dmclock_tpu.sim.dmc_sim import run_sim

        cfg = SimConfig(
            client_groups=1, server_groups=1,
            cli_group=[ClientGroup(
                client_count=2, client_total_ops=30,
                client_iops_goal=80.0, client_reservation=20.0,
                client_limit=100.0, client_weight=1.0,
                client_outstanding_ops=8,
                client_server_select_range=1)],
            srv_group=[ServerGroup(server_count=1, server_iops=200.0,
                                   server_threads=2)])
        sim = run_sim(cfg, model="dmclock-tpu", seed=3)
        chk = sim.report().ledger_check()
        assert chk is not None
        assert chk["mismatches"] == []
        assert chk["ops"] == 2 * 30
        # the oracle model has no backend ledger -> None path
        sim2 = run_sim(cfg, model="dmclock", seed=3)
        assert sim2.report().ledger_check() is None
        # ...but DOES materialize tags -> host tardiness percentiles
        pct = sim2.report().tardiness_percentiles()
        assert pct is not None and pct["count"] > 0
        rows = sim2.report().conformance()
        assert any(r["tardiness_max_ns"] >= 0 for r in rows)


# ----------------------------------------------------------------------
# registry export + healthz
# ----------------------------------------------------------------------

class TestRegistryExport:
    def test_publish_hists_prometheus_families(self):
        reg = MetricsRegistry()
        h = obshist.hist_zero()
        h = obshist.hist_observe(
            h, obshist.HIST_RESV_TARDINESS,
            jnp.asarray([1, 5, 1000], dtype=jnp.int64),
            jnp.ones(3, dtype=bool))
        obshist.publish_hists(reg, h, prefix="dmclock")
        text = reg.prometheus()
        assert "# TYPE dmclock_resv_tardiness_ns histogram" in text
        assert 'dmclock_resv_tardiness_ns_bucket{le="1"} 1' in text
        assert 'dmclock_resv_tardiness_ns_bucket{le="+Inf"} 3' in text
        assert "dmclock_resv_tardiness_ns_sum 1006" in text
        assert "dmclock_resv_tardiness_ns_count 3" in text
        # publish is a SET drain: re-publishing must not double-count
        obshist.publish_hists(reg, h, prefix="dmclock")
        assert "dmclock_resv_tardiness_ns_count 3" \
            in reg.prometheus()

    def test_publish_ledger_totals(self):
        reg = MetricsRegistry()
        led = obshist.ledger_zero(4).at[1].set(
            jnp.asarray([7, 3, 1, 90, 60], dtype=jnp.int64))
        obshist.publish_ledger(reg, led)
        snap = reg.snapshot()
        assert snap["dmclock_ledger_ops"][0]["value"] == 7
        assert snap["dmclock_ledger_tardiness_max_ns"][0]["value"] \
            == 60

    def test_healthz_endpoint(self):
        import urllib.request

        from dmclock_tpu.obs import MetricsHTTPServer

        reg = MetricsRegistry()
        with MetricsHTTPServer(reg, port=0) as srv:
            with urllib.request.urlopen(srv.healthz_url,
                                        timeout=5) as resp:
                assert resp.status == 200
                assert json.loads(resp.read()) == {"status": "ok"}

    def test_supervisor_healthz_probe(self):
        from dmclock_tpu.obs import MetricsHTTPServer
        from dmclock_tpu.robust.supervisor import _healthz_ok

        with MetricsHTTPServer(MetricsRegistry(), port=0) as srv:
            assert _healthz_ok(srv)
        assert not _healthz_ok(srv)      # closed server fails fast
