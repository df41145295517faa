"""Decision provenance plane tests (obs.provenance; docs/OBSERVABILITY.md
"Provenance plane").

The load-bearing contracts:

1. **On/off bit-identity** -- the provenance block must not perturb
   the decision stream or final state on any epoch engine or fast
   path (pure reductions over arrays the batches already
   materialize).
2. **Margin exactness** -- the sorted engines' per-decision margin is
   the EXACT runner-up distance (next sorted entry vs the served
   prefix's re-entry keys), pinned on a hand-built two-client race.
3. **Cross-loop exactness** -- the block's contents are bit-identical
   between the round and the stream loop, and crash equivalence
   extends to it (tests via robust.supervisor).
4. **Starvation detector** -- the last_served watermark and the
   once-per-episode client_starved warnings (fire on rising edge,
   re-arm on service).
5. **Flight overflow at stream-chunk boundaries** -- the newest-R
   contract holds when a single FUSED chunk commits more than R
   records, on all three engines (previously only exercised via the
   round loop).
6. **Trace schema v2** -- margin/eligible_depth/gate columns, the
   backward-compatible v1 reader, and the per-phase-vs-device-counters
   hard cross-check.
7. **explain.py** -- the seeded limit-starvation scenario attributes
   to limit_capped; synthetic window rows hit each cause.
"""

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.core import NS_PER_SEC
from dmclock_tpu.engine.fastpath import (scan_calendar_epoch,
                                         scan_chain_epoch,
                                         scan_prefix_epoch,
                                         speculate_prefix_batch)
from dmclock_tpu.obs import flight as obsflight
from dmclock_tpu.obs import histograms as obshist
from dmclock_tpu.obs import provenance as obsprov
from dmclock_tpu.obs import MetricsRegistry

from engine_helpers import deep_state, starvation_scenario

REPO = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "explain", REPO / "scripts" / "explain.py")
explain_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(explain_mod)

S = NS_PER_SEC

from dmclock_tpu.core import ClientInfo

INFOS = {
    0: ClientInfo(10.0, 2.0, 50.0),
    1: ClientInfo(5.0, 1.0, 40.0),
    2: ClientInfo(0.0, 3.0, 0.0),
}


def _mixed_state(depth=6):
    return deep_state(INFOS, depth)


def _digest(ep, fields):
    import hashlib

    h = hashlib.sha256()
    for f in fields:
        h.update(np.asarray(jax.device_get(getattr(ep, f))).tobytes())
    h.update(np.asarray(jax.device_get(
        jax.tree.leaves(ep.state)[0])).tobytes())
    return h.hexdigest()


ENGINES = {
    "prefix": (functools.partial(scan_prefix_epoch, m=3, k=16,
                                 anticipation_ns=0),
               ("count", "slot", "phase", "cost", "lb")),
    "chain": (functools.partial(scan_chain_epoch, m=3, k=8,
                                chain_depth=3, anticipation_ns=0),
              ("count", "slot", "cls", "length")),
    "calendar": (functools.partial(scan_calendar_epoch, m=2, steps=4,
                                   calendar_impl="minstop"),
                 ("count", "resv_count", "served")),
    "calendar-bucketed": (functools.partial(
        scan_calendar_epoch, m=2, steps=4, calendar_impl="bucketed",
        ladder_levels=3), ("count", "resv_count", "served")),
}


class TestBitIdentity:
    @pytest.mark.parametrize("name", sorted(ENGINES))
    def test_prov_on_off(self, name):
        fn, fields = ENGINES[name]
        off = jax.jit(fn)(_mixed_state(), jnp.int64(S))
        prov = obsprov.prov_init(64)
        on = jax.jit(lambda s, t: fn(s, t, prov=prov))(
            _mixed_state(), jnp.int64(S))
        assert _digest(off, fields) == _digest(on, fields)
        assert on.prov is not None and off.prov is None
        scal = np.asarray(jax.device_get(on.prov.scal))
        assert scal[obsprov.PS_BATCHES] > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("kw", [
        dict(select_impl="radix"), dict(tag_width=32)])
    def test_prov_on_off_fastpaths(self, kw):
        fn = functools.partial(scan_prefix_epoch, m=3, k=16,
                               anticipation_ns=0, **kw)
        off = jax.jit(fn)(_mixed_state(), jnp.int64(S))
        prov = obsprov.prov_init(64)
        on = jax.jit(lambda s, t: fn(s, t, prov=prov))(
            _mixed_state(), jnp.int64(S))
        fields = ("count", "slot", "phase", "cost", "lb")
        assert _digest(off, fields) == _digest(on, fields)

    def test_contents_equal_across_select_impls(self):
        """sort and radix commit identical decisions, so the
        provenance observations must be bit-identical too."""
        blocks = {}
        for impl in ("sort", "radix"):
            fn = functools.partial(scan_prefix_epoch, m=3, k=16,
                                   anticipation_ns=0,
                                   select_impl=impl)
            prov = obsprov.prov_init(64)
            ep = jax.jit(lambda s, t, fn=fn: fn(s, t, prov=prov))(
                _mixed_state(), jnp.int64(S))
            blocks[impl] = jax.device_get(ep.prov)
        for a, b in zip(blocks["sort"], blocks["radix"]):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestMarginExactness:
    def test_two_client_race(self):
        """Two weight-only clients with distinct proportion tags: the
        first decision's margin is the runner-up's tag distance."""
        infos = {0: ClientInfo(0.0, 4.0, 0.0),
                 1: ClientInfo(0.0, 1.0, 0.0)}
        st = deep_state(infos, 4)
        b = speculate_prefix_batch(st, jnp.int64(S), 8,
                                   anticipation_ns=0)
        margins = np.asarray(jax.device_get(b.margins))
        slots = np.asarray(jax.device_get(b.decisions.slot))
        count = int(jax.device_get(b.count))
        assert count >= 1
        # recompute the unified entry keys on the host: eff prop tag
        eff = np.asarray(jax.device_get(
            st.head_prop + st.prop_delta))
        # winner = lower eff tag; its exact runner-up is min(next
        # entry, its own... ) -- for two clients entering at distinct
        # tags, decision 0's runner-up is the OTHER client's entry
        # (both weight class), so margin0 == |eff delta| up to the
        # packed order bits (< 1 ns truncation)
        want = abs(int(eff[0]) - int(eff[1]))
        assert margins[0] >= 0
        assert abs(int(margins[0]) - want) <= 1, \
            (margins[:4].tolist(), want, slots[:4].tolist())

    def test_no_runner_up_records_nothing(self):
        """A sole candidate has no runner-up: margin -1, and the
        histogram stays empty."""
        infos = {0: ClientInfo(0.0, 4.0, 0.0)}
        st = deep_state(infos, 1)
        prov = obsprov.prov_init(64)
        ep = scan_prefix_epoch(st, jnp.int64(S), 1, 4,
                               anticipation_ns=0, prov=prov)
        h = np.asarray(jax.device_get(ep.prov.margin_hist))
        assert h[:obshist.NUM_BUCKETS].sum() == 0
        assert int(jax.device_get(ep.count).sum()) >= 1


class TestProvAlgebra:
    def test_combine_and_select(self):
        a = obsprov.prov_init(4)
        b = obsprov.ProvBlock(
            margin_hist=jnp.ones_like(a.margin_hist),
            scal=jnp.arange(obsprov.PS_FIELDS, dtype=jnp.int64),
            last_served=jnp.asarray([5, 0, 7, 0], jnp.int64))
        c = obsprov.prov_combine(a, b)
        assert np.array_equal(np.asarray(c.scal), np.asarray(b.scal))
        assert np.array_equal(np.asarray(c.last_served),
                              np.asarray(b.last_served))
        # max rows max, counter rows add
        d = obsprov.prov_combine(b, b)
        scal = np.asarray(d.scal)
        for i in range(obsprov.PS_FIELDS):
            want = i if i in (obsprov.PS_GATE_MAX,
                              obsprov.PS_ELIG_MAX,
                              obsprov.PS_STARVE_MAX) else 2 * i
            assert scal[i] == want, (i, scal[i], want)
        # liveness select: dead keeps OLD, live takes NEW
        dead = obsprov.prov_select(jnp.bool_(False), b, a)
        assert np.asarray(dead.scal).sum() == 0
        live = obsprov.prov_select(jnp.bool_(True), b, a)
        assert np.array_equal(np.asarray(live.scal),
                              np.asarray(b.scal))

    def test_init_baseline(self):
        """A block armed mid-run measures staleness from its own
        creation time, not from virtual t=0 (a post-warm-up reset
        must not read continuously-served
        clients as starved since the beginning of the run)."""
        prov = obsprov.prov_init(3, now_ns=500)
        assert np.asarray(prov.last_served).tolist() == [500] * 3
        newp = obsprov.prov_observe(
            prov, now=jnp.int64(700),
            elig=jnp.asarray([True, True, False]),
            gated=jnp.zeros(3, bool), win_cls=jnp.int32(1),
            served_pc=jnp.zeros(3, jnp.int32))
        scal = np.asarray(jax.device_get(newp.scal))
        assert scal[obsprov.PS_STARVE_MAX] == 200   # 700 - 500, not 700
        rows = obsprov.stale_clients(prov, 700, 100,
                                     backlog=np.asarray([1, 0, 0]))
        assert [r["client"] for r in rows] == [0]
        assert rows[0]["stale_ns"] == 200

    def test_dict_and_publish(self):
        prov = obsprov.prov_init(4)
        d = obsprov.prov_dict(prov)
        assert d["batches"] == 0 and d["margin_p50_ns"] == 0.0
        reg = MetricsRegistry()
        obsprov.publish_provenance(reg, prov)
        names = {m.name for m in reg.metrics()}
        assert "dmclock_provenance_margin_p99_ns" in names
        assert "dmclock_starvation_max_ns" in names


class TestStarvation:
    def test_last_served_watermark(self):
        """Clients served this epoch stamp now; unserved keep their
        old watermark and grow the starvation max."""
        st = _mixed_state()
        prov = obsprov.prov_init(64)
        ep = scan_prefix_epoch(st, jnp.int64(S), 2, 8,
                               anticipation_ns=0, prov=prov)
        last = np.asarray(jax.device_get(ep.prov.last_served))
        slots = np.asarray(jax.device_get(ep.slot)).ravel()
        served = set(int(s) for s in slots if s >= 0)
        for c in served:
            assert last[c] == S
        assert int(np.asarray(jax.device_get(
            ep.prov.scal))[obsprov.PS_STARVE_MAX]) == S

    def test_monitor_once_per_episode(self):
        fired_log = []
        mon = obsprov.StarvationMonitor(100, log=fired_log.append)
        prov = obsprov.prov_init(3)
        backlog = np.asarray([1, 1, 0])
        # client 0 and 1 backlogged and stale; 2 idle-stale (ignored)
        w1 = mon.observe(prov, 500, backlog=backlog)
        assert {w["client"] for w in w1} == {0, 1}
        # same stale set: no re-fire
        assert mon.observe(prov, 600, backlog=backlog) == []
        # client 0 served (watermark catches up): episode re-arms
        prov2 = prov._replace(
            last_served=jnp.asarray([590, 0, 0], jnp.int64))
        assert mon.observe(prov2, 600, backlog=backlog) == []
        w3 = mon.observe(prov2, 800, backlog=backlog)
        assert {w["client"] for w in w3} == {0}
        assert mon.episodes_total == 3
        assert len(fired_log) == 3

    def test_monitor_routes_through_watchdog(self):
        class FakeWd:
            def __init__(self):
                self.warnings = []

            def external_warning(self, obj):
                self.warnings.append(obj)

        wd = FakeWd()
        mon = obsprov.StarvationMonitor(10, watchdog=wd)
        mon.observe(obsprov.prov_init(2), 100,
                    backlog=np.asarray([1, 0]))
        assert len(wd.warnings) == 1
        assert wd.warnings[0]["kind"] == "client_starved"


class TestFlightChunkOverflow:
    """Satellite: the newest-R-on-overflow contract when a single
    FUSED stream chunk commits more than R records, on all three
    engines (previously only exercised via the round loop)."""

    @pytest.mark.parametrize("engine,kw", [
        ("prefix", dict(k=8)),
        ("chain", dict(k=8, chain_depth=2)),
        ("calendar", dict(k=3)),
    ])
    def test_one_chunk_overflow_keeps_newest(self, engine, kw):
        from dmclock_tpu.robust.guarded import run_stream_chunk_guarded

        R = 4
        st = _mixed_state(depth=8)
        fl = obsflight.flight_init(R)
        g = run_stream_chunk_guarded(
            st, 0, None, engine=engine, epochs=3, m=2,
            dt_epoch_ns=S, waves=2, flight=fl, **kw)
        assert g.stream_fallback == 0
        seq = int(jax.device_get(g.flight.seq))
        total = sum(g.counts) if engine == "prefix" else seq
        assert seq > R, (engine, seq)
        recs = obsflight.flight_drain(g.flight)
        assert len(recs) == R
        # newest R, contiguous, ending at the final record
        assert [r["seq"] for r in recs] == list(range(seq - R, seq))
        if engine == "prefix":
            assert seq == total   # one record per decision
        # the provenance columns ride every record
        assert all("margin" in r and "gate" in r for r in recs)

    def test_chunk_overflow_matches_round_loop(self):
        """The ring after one fused chunk == the ring after the same
        epochs on the round loop (newest-R is loop-invariant)."""
        from dmclock_tpu.robust.guarded import (run_epoch_guarded,
                                                run_stream_chunk_guarded)

        R = 4
        g = run_stream_chunk_guarded(
            _mixed_state(depth=8), 0, None, engine="prefix",
            epochs=3, m=2, k=8, dt_epoch_ns=S, waves=2,
            flight=obsflight.flight_init(R))
        st = _mixed_state(depth=8)
        fl = obsflight.flight_init(R)
        for e in range(3):
            ep = run_epoch_guarded(st, (e + 1) * S, engine="prefix",
                                   m=2, k=8, flight=fl)
            st, fl = ep.state, ep.flight
        assert np.array_equal(
            np.asarray(jax.device_get(g.flight.buf)),
            np.asarray(jax.device_get(fl.buf)))
        assert int(jax.device_get(g.flight.seq)) == \
            int(jax.device_get(fl.seq))


class TestTraceV2:
    def test_writer_reader_round_trip(self, tmp_path):
        from dmclock_tpu.obs.trace import (DecisionTrace, load_trace,
                                           validate_trace_file)

        p = tmp_path / "t.jsonl"
        with DecisionTrace(str(p)) as tr:
            tr.record(1, 0, 7, 0, 2, tag=(1, 2, 3), margin=100,
                      eligible_depth=5, gate=1)
            tr.record(2, 0, 8, 1, 1)
        stats = validate_trace_file(str(p))
        assert stats["rows"] == 2 and stats["v2_rows"] == 2
        assert stats["margin"] == {"count": 1, "max_ns": 100}
        rows = load_trace(str(p))
        assert rows[0]["margin"] == 100 and rows[1]["margin"] is None

    def test_v1_rows_load_with_nulls(self, tmp_path):
        p = tmp_path / "v1.jsonl"
        p.write_text(json.dumps(
            {"t": 1, "server": 0, "client": 3,
             "phase": "priority", "cost": 1, "tag": None}) + "\n")
        from dmclock_tpu.obs.trace import load_trace, validate_trace_file

        stats = validate_trace_file(str(p))
        assert stats["v1_rows"] == 1 and stats["v2_rows"] == 0
        rows = load_trace(str(p))
        assert rows[0]["margin"] is None
        assert rows[0]["eligible_depth"] is None

    def test_bad_provenance_type_rejected(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps(
            {"t": 1, "server": 0, "client": 3, "phase": "priority",
             "cost": 1, "tag": None, "margin": "fast",
             "eligible_depth": None, "gate": None}) + "\n")
        from dmclock_tpu.obs.trace import validate_trace_file

        with pytest.raises(ValueError, match="margin"):
            validate_trace_file(str(p))

    def test_summarize_device_cross_check(self, tmp_path):
        from dmclock_tpu.obs.trace import DecisionTrace, summarize

        p = tmp_path / "t.jsonl"
        with DecisionTrace(str(p)) as tr:
            tr.record(1, 0, 0, 0, 1)   # reservation
            tr.record(2, 0, 1, 1, 1)   # priority
        assert summarize(str(p), (1, 1))["per_phase"] == \
            {"reservation": 1, "priority": 1}
        with pytest.raises(ValueError, match="diverge"):
            summarize(str(p), (2, 0))


def _win(client=0, **kw):
    base = dict(seq=0, client=client, contract_epoch=1, e0=0, e1=2,
                ops=4, cost=4, resv_ops=0, tardy_ops=0, lb_ops=0,
                tardiness_sum_ns=0, backlog=0, window_s=0.2,
                rate=20.0, reservation=0.0, weight=1.0, limit=0.0,
                share=0.25, entitled_share=0.25, share_err=0.0,
                resv_deficit=0.0, resv_miss=False, limit_excess=0.0,
                tardiness_mean_ns=0.0)
    base.update(kw)
    return base


class TestExplain:
    def test_no_demand(self):
        res = explain_mod.attribute([_win(ops=0, rate=0.0, backlog=0,
                                          share=0.0)])
        assert res["cause"] == "no_demand"

    def test_limit_capped(self):
        res = explain_mod.attribute([_win(limit=20.0, rate=18.0,
                                          backlog=9, share=0.2,
                                          entitled_share=0.5)])
        assert res["cause"] == "limit_capped"
        assert res["scores"]["limit_capped"] >= 0.8

    def test_out_competed(self):
        res = explain_mod.attribute([_win(share=0.1,
                                          entitled_share=0.4,
                                          share_err=-0.75,
                                          backlog=12)])
        assert res["cause"] == "out_competed"

    def test_reservation_tardy(self):
        res = explain_mod.attribute([_win(reservation=50.0,
                                          resv_ops=10, tardy_ops=8,
                                          resv_deficit=30.0,
                                          resv_miss=True, backlog=3)])
        assert res["cause"] == "reservation_tardy"

    def test_conforming_null(self):
        res = explain_mod.attribute([_win()])
        assert res["cause"] == "conforming"

    def test_scenario_round(self, tmp_path):
        slo_log = str(tmp_path / "slo.jsonl")
        fl = str(tmp_path / "flight.jsonl")
        prov, plane, st, now = starvation_scenario(
            "prefix", "round", slo_log=slo_log, flight_dump=fl)
        res = explain_mod.explain(slo_log, 0, flight_path=fl)
        assert res["cause"] == "limit_capped"
        assert res["scores"]["limit_capped"] > 0.5
        # the competitor is NOT limit-capped
        res1 = explain_mod.explain(slo_log, 1)
        assert res1["cause"] != "limit_capped"
        # the plane saw the gate pressure live
        pd = obsprov.prov_dict(prov)
        assert pd["limit_gate_share"] > 0.25
        assert pd["gated_batches"] > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("engine", ["chain", "calendar"])
    def test_scenario_engines(self, engine, tmp_path):
        slo_log = str(tmp_path / "slo.jsonl")
        starvation_scenario(engine, "round", slo_log=slo_log)
        res = explain_mod.explain(slo_log, 0)
        assert res["cause"] == "limit_capped"

    @pytest.mark.slow
    def test_scenario_stream_and_diff(self, tmp_path):
        a = str(tmp_path / "a.jsonl")
        b = str(tmp_path / "b.jsonl")
        starvation_scenario("prefix", "stream", slo_log=a)
        starvation_scenario("prefix", "round", slo_log=b)
        res = explain_mod.explain(a, 0)
        assert res["cause"] == "limit_capped"
        # identical runs under --diff: zero score deltas
        base = explain_mod.explain(b, 0)
        assert base["scores"] == res["scores"]


class TestSupervisorProv:
    def test_round_equals_stream(self):
        import dataclasses

        from dmclock_tpu.robust import supervisor as SV

        job = SV.EpochJob(engine="prefix", k=16, n=96, depth=6,
                          ring=12, epochs=4, m=2, seed=9,
                          arrival_lam=1.5, waves=3, ckpt_every=2,
                          with_prov=True)
        r = SV.run_job(job)
        s = SV.run_job(dataclasses.replace(job, engine_loop="stream"))
        assert r.digest == s.digest
        for f in ("prov_margin_hist", "prov_scal",
                  "prov_last_served"):
            assert np.array_equal(getattr(r, f), getattr(s, f)), f

    @pytest.mark.slow
    def test_crash_equivalence(self, tmp_path):
        from dmclock_tpu.robust import host_faults as HF
        from dmclock_tpu.robust import supervisor as SV

        job = SV.EpochJob(engine="calendar", calendar_impl="bucketed",
                          ladder_levels=2, k=4, n=96, depth=6,
                          ring=12, epochs=4, m=2, seed=9,
                          arrival_lam=1.5, waves=3, ckpt_every=2,
                          with_prov=True, flight_records=16)
        ref = SV.run_job(job)
        plan = HF.HostFaultPlan(
            kill_at_decisions=(ref.decisions // 2,))
        got = SV.run_supervised(job, str(tmp_path), plan)
        SV.assert_crash_equivalent(got, ref)

    def test_prov_off_side_none(self):
        from dmclock_tpu.robust import supervisor as SV

        job = SV.EpochJob(engine="prefix", k=16, n=64, depth=4,
                          ring=8, epochs=2, m=2, seed=9,
                          arrival_lam=1.0, waves=2, ckpt_every=2)
        r = SV.run_job(job)
        assert r.prov_scal is None

    def test_prov_survives_with_slo(self):
        """Regression: a job running BOTH planes must report both --
        the slo branch of _build_result once rebound the kwargs dict
        and silently dropped the prov_* fields (which would make the
        crash-equivalence comparison vacuous for the combination)."""
        from dmclock_tpu.robust import supervisor as SV

        job = SV.EpochJob(engine="prefix", k=16, n=64, depth=4,
                          ring=8, epochs=4, m=2, seed=9,
                          arrival_lam=1.0, waves=2, ckpt_every=2,
                          with_prov=True, with_slo=True)
        r = SV.run_job(job)
        assert r.prov_scal is not None and r.slo is not None
        assert r.prov_margin_hist is not None
        assert r.prov_last_served is not None
        import dataclasses

        s = SV.run_job(dataclasses.replace(job, engine_loop="stream"))
        assert np.array_equal(r.prov_scal, s.prov_scal)

    def test_churn_plus_prov_composes(self):
        """The lifecycle boundary carries the provenance watermark
        through grow/compact/evict as a boundary ``extras`` rider
        (the lifted PR-12 rejection): the combination runs, reports
        the prov arrays, and stays loop-identical.  The deeper
        churn-storm + crash-equivalence gates live in
        tests/test_controller.py::TestChurnProvComposition."""
        import dataclasses

        from dmclock_tpu.lifecycle import make_spec
        from dmclock_tpu.robust import supervisor as SV

        spec = make_spec("flash_crowd", total_ids=8)
        job = SV.EpochJob(engine="prefix", k=8, churn=spec,
                          epochs=4, m=2, ckpt_every=2,
                          with_prov=True)
        r = SV.run_job(job)
        assert r.prov_scal is not None
        s = SV.run_job(dataclasses.replace(job, engine_loop="stream"))
        assert r.digest == s.digest
        assert np.array_equal(r.prov_scal, s.prov_scal)


class TestShardPressure:
    def test_pressure_vec_semantics(self):
        st = _mixed_state(depth=6)
        vec = np.asarray(jax.device_get(
            obsprov.pressure_vec(st, jnp.int64(S))))
        assert vec[obsprov.PRESS_BACKLOG] == \
            int(np.asarray(jax.device_get(st.depth)).sum())
        assert vec[obsprov.PRESS_ELIG] == vec[obsprov.PRESS_ELIG_PEAK]
        assert vec[obsprov.PRESS_WAIT_WM] >= 0

    def test_combine_axis_and_publish(self):
        mat = jnp.asarray([[4, 10, 4, 100], [2, 6, 2, 300]],
                          jnp.int64)
        red = np.asarray(jax.device_get(
            obsprov.pressure_combine_axis(mat)))
        assert red.tolist() == [6, 16, 4, 300]
        reg = MetricsRegistry()
        obsprov.publish_shard_pressure(reg, np.asarray(mat), red)
        names = {m.name for m in reg.metrics()}
        assert "dmclock_shard_pressure_eligible_live" in names
        assert "dmclock_shard_pressure_head_wait_max_ns" in names

    def test_cluster_step_pressure(self):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 (virtual) devices")
        from dmclock_tpu.core.timebase import rate_to_inv_ns
        from dmclock_tpu.parallel import cluster as CL

        S_, C = 4, 8
        mesh = CL.make_mesh(4)
        cl = CL.init_cluster(S_, C)
        cl = CL.install_clients(
            cl, jnp.asarray([rate_to_inv_ns(10.0)] * C, jnp.int64),
            jnp.asarray([rate_to_inv_ns(1.0 + (i % 3))
                         for i in range(C)], jnp.int64),
            jnp.zeros((C,), jnp.int64))
        cl = CL.shard_cluster(cl, mesh)
        arr = jnp.ones((S_, C), jnp.int32)
        out = CL.cluster_step(cl, arr, 1, mesh, decisions_per_step=4,
                              advance_ns=10 ** 8, with_pressure=True)
        cl2, decs, press, merged = out
        press = np.asarray(jax.device_get(press))
        merged = np.asarray(jax.device_get(merged))
        assert press.shape == (S_, obsprov.PRESS_FIELDS)
        assert merged[obsprov.PRESS_BACKLOG] == \
            press[:, obsprov.PRESS_BACKLOG].sum()
        assert merged[obsprov.PRESS_WAIT_WM] == \
            press[:, obsprov.PRESS_WAIT_WM].max()
        # decisions identical to the no-pressure step
        cl3, decs2 = CL.cluster_step(cl, arr, 1, mesh,
                                     decisions_per_step=4,
                                     advance_ns=10 ** 8)
        for a, b in zip(jax.tree.leaves(decs),
                        jax.tree.leaves(decs2)):
            assert np.array_equal(np.asarray(jax.device_get(a)),
                                  np.asarray(jax.device_get(b)))
