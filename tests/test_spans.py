"""Tracing-plane tests (obs.spans / trace_export / watchdog +
wire-through): tracer semantics, Perfetto export + validation,
trace_report aggregation/diff, the bit-identical-decisions contract on
the queue and the guarded epoch runner, and the supervisor span_log's
crash survival."""

import importlib.util
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dmclock_tpu.obs import spans as S
from dmclock_tpu.obs import trace_export as TE
from dmclock_tpu.obs.registry import MetricsRegistry
from dmclock_tpu.obs.watchdog import Watchdog

REPO = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "trace_report", REPO / "scripts" / "trace_report.py")
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


def make_clock(start=0):
    """Deterministic injectable ns clock."""
    state = {"t": start}

    def clock():
        return state["t"]

    def advance(ns):
        state["t"] += ns

    return clock, advance


class TestSpanTracer:
    def test_nesting_self_time(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        with tr.span("outer", "host_prep"):
            adv(10)
            with tr.span("inner", "dispatch"):
                adv(30)
            adv(5)
        rows = tr.rows()
        assert [r["name"] for r in rows] == ["inner", "outer"]
        inner, outer = rows
        assert inner["dur"] == 30 and inner["self"] == 30
        assert inner["depth"] == 1
        assert outer["dur"] == 45 and outer["self"] == 15
        cats = tr.category_totals()
        assert cats["host_prep"] == 15 and cats["dispatch"] == 30

    def test_instant_and_args(self):
        tr = S.SpanTracer()
        tr.instant("mark", "retry", error="Boom")
        (row,) = tr.rows()
        assert row["dur"] == 0 and row["args"] == {"error": "Boom"}

    def test_unknown_category_rejected(self):
        # ValueError, not assert: must survive PYTHONOPTIMIZE
        tr = S.SpanTracer()
        with pytest.raises(ValueError, match="categories"):
            tr.span("x", "not-a-category")
        with pytest.raises(ValueError, match="categories"):
            tr.instant("x", "also-wrong")

    def test_null_guard_is_noop(self):
        with S.span(None, "x", "dispatch"):
            pass
        S.instant(None, "x", "retry")   # no raise, nothing recorded

    def test_ring_bound_drops_oldest_keeps_aggregates(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(limit=4, clock_ns=clock)
        for i in range(10):
            with tr.span(f"s{i}", "drain"):
                adv(7)
        assert len(tr.rows()) == 4
        assert tr.spans_recorded == 10
        assert tr.spans_dropped == 6
        # aggregates are exact past the wrap
        assert tr.category_totals()["drain"] == 70
        assert tr.category_counts()["drain"] == 10

    def test_thread_safety_and_per_thread_stacks(self):
        tr = S.SpanTracer()

        def worker():
            for _ in range(200):
                with tr.span("w", "fetch"):
                    with tr.span("w2", "drain"):
                        pass

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tr.spans_recorded == 4 * 200 * 2
        assert tr.category_counts()["fetch"] == 800
        # depths never interleave across threads
        assert all(r["depth"] == (1 if r["name"] == "w2" else 0)
                   for r in tr.rows())

    def test_drain_jsonl_appends_and_clears(self, tmp_path):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        path = str(tmp_path / "spans.jsonl")
        with tr.span("a", "checkpoint"):
            adv(5)
        assert tr.drain_jsonl(path) == 1
        assert tr.rows() == []
        with tr.span("b", "checkpoint"):
            adv(5)
        assert tr.drain_jsonl(path) == 2 - 1
        rows = S.load_jsonl(path)
        assert [r["name"] for r in rows] == ["a", "b"]

    def test_leaked_child_tolerated_and_counted(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        outer = tr.span("outer", "host_prep")
        inner = tr.span("inner", "dispatch")
        outer.__enter__()
        inner.__enter__()
        adv(10)
        # exiting the OUTER span with the inner still open must not
        # corrupt the stack -- and the lost child is COUNTED
        outer.__exit__(None, None, None)
        assert tr.rows()[-1]["name"] == "outer"
        assert tr.spans_leaked == 1
        # the leaked child's late exit is a discipline break too, not
        # a fabricated second row
        n_rows = len(tr.rows())
        inner.__exit__(None, None, None)
        assert len(tr.rows()) == n_rows
        assert tr.spans_leaked == 2
        with tr.span("next", "fetch"):
            adv(1)
        assert tr.rows()[-1]["depth"] == 0
        assert tr.summary()["leaked"] == 2

    def test_double_exit_counts_not_duplicates(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        sp = tr.span("s", "drain")
        sp.__enter__()
        adv(5)
        sp.__exit__(None, None, None)
        sp.__exit__(None, None, None)
        assert len(tr.rows()) == 1
        assert tr.spans_leaked == 1

    def test_every_pop_closes_its_annotation(self, monkeypatch):
        """Each span is a profiler TraceAnnotation from push to pop,
        and a popped-through (leaked) child closes its own too."""
        events = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append(("enter", self.name))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

        monkeypatch.setattr(S, "TraceAnnotation", Ann)
        tr = S.SpanTracer()
        with tr.span("a", "host_prep"):
            pass
        outer = tr.span("outer", "host_prep")
        outer.__enter__()
        tr.span("leaked", "dispatch").__enter__()
        outer.__exit__(None, None, None)
        assert events == [("enter", "a"), ("exit", "a"),
                          ("enter", "outer"), ("enter", "leaked"),
                          ("exit", "leaked"), ("exit", "outer")]

    def test_spans_land_on_the_profiler_trace(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp

        tr = S.SpanTracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("test.on_profiler_clock", "host_prep"):
                jnp.arange(8).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in pd.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert "test.on_profiler_clock" in names
        assert tr.rows()[-1]["name"] == "test.on_profiler_clock"


class TestChromeExport:
    def _tracer(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        with tr.span("epoch", "host_prep"):
            adv(1000)
            with tr.span("launch", "dispatch"):
                adv(2000)
            with tr.span("wait", "device_compute"):
                adv(5000)
        return tr

    def test_export_validates(self, tmp_path):
        tr = self._tracer()
        path = str(tmp_path / "t.json")
        n = TE.export_chrome_trace(tr, path, metadata={"who": "test"})
        assert n == 3
        stats = TE.validate_chrome_trace(path)
        assert stats["events"] == 3 and stats["tids"] == 1
        # self-time sums match the tracer's category totals (ns)
        for cat, ns in tr.category_totals().items():
            if ns:
                assert stats["cat_self_ns"][cat] == pytest.approx(
                    ns, rel=1e-9)

    def test_export_loads_as_chrome_json(self, tmp_path):
        path = str(tmp_path / "t.json")
        TE.export_chrome_trace(self._tracer(), path)
        obj = json.load(open(path))
        assert {e["ph"] for e in obj["traceEvents"]} == {"X"}
        # sorted by ts; parent-first at equal ts
        ts = [e["ts"] for e in obj["traceEvents"]]
        assert ts == sorted(ts)

    def test_validator_rejects_bad_category(self, tmp_path):
        path = str(tmp_path / "bad.json")
        json.dump({"traceEvents": [
            {"name": "x", "cat": "mystery", "ph": "X", "ts": 0,
             "dur": 1, "pid": 0, "tid": 0}]}, open(path, "w"))
        with pytest.raises(ValueError, match="categories"):
            TE.validate_chrome_trace(path)

    def test_validator_rejects_partial_overlap(self, tmp_path):
        path = str(tmp_path / "overlap.json")
        json.dump({"traceEvents": [
            {"name": "a", "cat": "dispatch", "ph": "X", "ts": 0.0,
             "dur": 10.0, "pid": 0, "tid": 0},
            {"name": "b", "cat": "dispatch", "ph": "X", "ts": 5.0,
             "dur": 10.0, "pid": 0, "tid": 0}]}, open(path, "w"))
        with pytest.raises(ValueError, match="nested"):
            TE.validate_chrome_trace(path)

    def test_validator_rejects_ts_regression(self, tmp_path):
        path = str(tmp_path / "regress.json")
        json.dump({"traceEvents": [
            {"name": "a", "cat": "dispatch", "ph": "X", "ts": 10.0,
             "dur": 1.0, "pid": 0, "tid": 0},
            {"name": "b", "cat": "dispatch", "ph": "X", "ts": 0.0,
             "dur": 1.0, "pid": 0, "tid": 0}]}, open(path, "w"))
        with pytest.raises(ValueError, match="regressed"):
            TE.validate_chrome_trace(path)

    def test_load_rows_roundtrip_both_formats(self, tmp_path):
        tr = self._tracer()
        cj = str(tmp_path / "t.json")
        jl = str(tmp_path / "t.jsonl")
        TE.export_chrome_trace(tr, cj)
        tr.export_jsonl(jl)
        a = TE.load_rows(cj)
        b = TE.load_rows(jl)
        assert len(a) == len(b) == 3
        assert sorted(r["name"] for r in a) == \
            sorted(r["name"] for r in b)
        assert {r["cat"] for r in a} == {r["cat"] for r in b}


class TestTraceReport:
    def _write_trace(self, tmp_path, name="t.json"):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        for _ in range(4):
            with tr.span("round", "dispatch"):
                adv(17_000_000)
            with tr.span("sync", "device_compute"):
                adv(3_000_000)
        path = str(tmp_path / name)
        TE.export_chrome_trace(tr, path)
        return path

    def test_report_table_and_ratio(self, tmp_path, capsys):
        path = self._write_trace(tmp_path)
        assert trace_report.main([path]) == 0
        out = capsys.readouterr().out
        assert "round" in out and "dispatch" in out
        # 4x17ms dispatch vs 4x3ms compute
        assert "dispatch-vs-compute ratio: 5.667" in out
        assert "ns/dec" not in out      # amortized column is opt-in

    def test_report_per_decision_amortized_column(self, tmp_path,
                                                  capsys):
        # --decisions N: the loop-structure-independent cost view
        # when one stream launch covers a whole chunk of rounds
        path = self._write_trace(tmp_path)
        assert trace_report.main([path, "--decisions",
                                  "1000000"]) == 0
        out = capsys.readouterr().out
        assert "ns/dec" in out
        # 4 x 17ms dispatch self over 1M decisions = 68 ns/decision
        assert "dispatch amortized: 68.0 ns/decision" in out

    def test_aggregate_self_time_sweep_on_chrome_rows(self, tmp_path):
        # chrome rows carry no "self": the sweep must subtract
        # children from parents
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        with tr.span("outer", "host_prep"):
            adv(10_000)
            with tr.span("inner", "dispatch"):
                adv(40_000)
        path = str(tmp_path / "n.json")
        TE.export_chrome_trace(tr, path)
        agg = trace_report.aggregate(TE.load_rows(path))
        assert agg[("outer", "host_prep")]["self_ns"] == \
            pytest.approx(10_000)
        assert agg[("inner", "dispatch")]["self_ns"] == \
            pytest.approx(40_000)

    def test_diff_mode(self, tmp_path, capsys):
        a = self._write_trace(tmp_path, "a.json")
        # baseline with a heavier dispatch tax
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        for _ in range(4):
            with tr.span("round", "dispatch"):
                adv(60_000_000)
            with tr.span("sync", "device_compute"):
                adv(3_000_000)
        b = str(tmp_path / "b.json")
        TE.export_chrome_trace(tr, b)
        assert trace_report.main([a, "--diff", b]) == 0
        out = capsys.readouterr().out
        assert "span diff" in out
        assert "-172.00" in out     # 4 x (17-60) ms of dispatch self
        assert "dispatch-vs-compute ratio: 20.000 -> 5.667" in out

    def test_bad_input_exits_2(self, tmp_path, capsys):
        missing = str(tmp_path / "nope.json")
        assert trace_report.main([missing]) == 2


class TestWatchdog:
    def test_dispatch_share_warning(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        logs = []
        reg = MetricsRegistry()
        wd = Watchdog(tr, dispatch_share_warn=0.5, registry=reg,
                      log=logs.append, clock_ns=clock)
        with tr.span("l", "dispatch"):
            adv(90_000_000)
        with tr.span("w", "device_compute"):
            adv(10_000_000)
        warns = wd.poll_once()
        assert [w["kind"] for w in warns] == ["dispatch_share"]
        assert warns[0]["share"] == pytest.approx(0.9)
        assert logs and logs[0].startswith("# watchdog:")
        assert reg.counter(
            "dmclock_watchdog_warnings_total").value == 1
        # still breaching: same episode, no warning spam
        with tr.span("l", "dispatch"):
            adv(90_000_000)
        with tr.span("w", "device_compute"):
            adv(10_000_000)
        assert wd.poll_once() == []
        # healthy window resets the episode...
        with tr.span("l", "dispatch"):
            adv(10_000_000)
        with tr.span("w", "device_compute"):
            adv(90_000_000)
        assert wd.poll_once() == []
        # ...so a fresh breach warns again
        with tr.span("l", "dispatch"):
            adv(90_000_000)
        with tr.span("w", "device_compute"):
            adv(10_000_000)
        assert [w["kind"] for w in wd.poll_once()] == \
            ["dispatch_share"]

    def test_share_not_judged_mid_chain(self):
        # the chained-launch wiring records device time only at chain
        # ends: a poll window with dispatch spans but NO completed
        # device span must not warn (it would fire on every healthy
        # mid-chain poll)
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, dispatch_share_warn=0.5,
                      log=lambda _s: None, clock_ns=clock)
        with tr.span("l", "dispatch"):
            adv(500_000_000)
        assert wd.poll_once() == []
        # the chain-end window (device span completes) IS judged
        with tr.span("l", "dispatch"):
            adv(500_000_000)
        with tr.span("w", "device_compute"):
            adv(100_000_000)
        assert [w["kind"] for w in wd.poll_once()] == \
            ["dispatch_share"]

    def test_skipped_windows_accumulate_into_judged_one(self):
        # mid-chain polls must NOT advance the share baseline: a
        # chain paying 3s dispatch / 1s device across several polls
        # breaches 0.6 even though the final window alone would not
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, dispatch_share_warn=0.6,
                      log=lambda _s: None, clock_ns=clock)
        for _ in range(3):      # mid-chain: dispatch only, skipped
            with tr.span("l", "dispatch"):
                adv(1_000_000_000)
            assert wd.poll_once() == []
        # chain end: 0.5s more dispatch + the 1s digest sync; window
        # = 3.5s dispatch vs 1s device -> share 0.78
        with tr.span("l", "dispatch"):
            adv(500_000_000)
        with tr.span("w", "device_compute"):
            adv(1_000_000_000)
        (w,) = wd.poll_once()
        assert w["kind"] == "dispatch_share"
        assert w["share"] == pytest.approx(3.5 / 4.5, abs=1e-3)

    def test_launch_stall_warns_once_per_episode(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, stall_after_s=1.0, log=lambda _s: None,
                      dispatch_share_warn=2.0,   # share check off:
                      clock_ns=clock)            # stall only
        with tr.span("l", "dispatch"):
            adv(1_000_000)
        assert wd.poll_once() == []          # fresh launch
        adv(2_000_000_000)
        (w,) = wd.poll_once()
        assert w["kind"] == "launch_stall"
        assert wd.poll_once() == []          # same episode: no spam
        with tr.span("l", "dispatch"):       # cadence resumes
            adv(1_000_000)
        assert wd.poll_once() == []
        adv(2_000_000_000)
        assert [w["kind"] for w in wd.poll_once()] == ["launch_stall"]

    def test_no_stall_before_first_launch(self):
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, stall_after_s=1.0, log=lambda _s: None,
                      clock_ns=clock)
        adv(10_000_000_000)
        assert wd.poll_once() == []

    def test_no_stall_while_stream_launch_in_flight(self):
        # the streaming regression (docs/OBSERVABILITY.md): a fused
        # stream chunk legitimately runs for SECONDS inside one
        # launch -- the dispatch span completed long ago, but the
        # host sits inside an open device_wait span.  The watchdog
        # must read the open span as a live cadence, not a stall.
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, stall_after_s=1.0, log=lambda _s: None,
                      dispatch_share_warn=2.0, clock_ns=clock)
        with tr.span("stream.dispatch", "dispatch"):
            adv(1_000_000)
        sp = tr.span("stream.device_wait", "device_compute")
        sp.__enter__()
        adv(5_000_000_000)              # deep inside the fused chunk
        assert wd.poll_once() == [], \
            "launch_stall false-fired on a healthy in-flight chunk"
        sp.__exit__(None, None, None)
        # with the launch closed and no heartbeat, real silence still
        # warns (the fix must not blind the stall check)
        adv(5_000_000_000)
        assert [w["kind"] for w in wd.poll_once()] == ["launch_stall"]

    def test_wedged_launch_still_warns(self):
        # the suppression is BOUNDED: a launch the runtime wedged
        # INSIDE (an open device_wait older than in_flight_max_s)
        # must stop suppressing -- a wedged launch is the original
        # failure mode the stall check exists for
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, stall_after_s=1.0, in_flight_max_s=8.0,
                      log=lambda _s: None, dispatch_share_warn=2.0,
                      clock_ns=clock)
        with tr.span("stream.dispatch", "dispatch"):
            adv(1_000_000)
        sp = tr.span("stream.device_wait", "device_compute")
        sp.__enter__()
        adv(5_000_000_000)
        assert wd.poll_once() == []          # young launch: healthy
        adv(5_000_000_000)                   # 10s open > 8s threshold
        assert [w["kind"] for w in wd.poll_once()] == ["launch_stall"]
        sp.__exit__(None, None, None)

    def test_dead_thread_orphan_spans_pruned(self):
        # a thread that exits with a span still open must not report
        # in-flight work forever (it would permanently blind the
        # stall check); its stack prunes on the next walk and the
        # loss is counted
        tr = S.SpanTracer()

        def leaky():
            tr.span("w", "device_compute").__enter__()   # never exits

        t = threading.Thread(target=leaky)
        t.start()
        t.join(5)
        assert tr.open_categories() == {}
        assert tr.oldest_open_ns() is None
        assert tr.spans_leaked >= 1

    def test_no_stall_with_stream_heartbeat(self):
        # the drain-point heartbeat: the stream loop emits a
        # drain-category instant at every chunk drain; recent drain
        # activity proves the serve loop alive between launches
        clock, adv = make_clock()
        tr = S.SpanTracer(clock_ns=clock)
        wd = Watchdog(tr, stall_after_s=1.0, log=lambda _s: None,
                      dispatch_share_warn=2.0, clock_ns=clock)
        with tr.span("stream.dispatch", "dispatch"):
            adv(1_000_000)
        adv(900_000_000)
        tr.instant("stream.heartbeat", "drain", epoch=2)
        adv(900_000_000)                # dispatch silent 1.8s, but the
        assert wd.poll_once() == []     # heartbeat is 0.9s fresh
        adv(2_000_000_000)              # heartbeat stale too: stall
        assert [w["kind"] for w in wd.poll_once()] == ["launch_stall"]

    def test_open_categories_cross_thread(self):
        tr = S.SpanTracer()
        assert tr.open_categories() == {}
        entered = threading.Event()
        release = threading.Event()

        def worker():
            with tr.span("w", "device_compute"):
                entered.set()
                release.wait(5)

        t = threading.Thread(target=worker)
        t.start()
        entered.wait(5)
        with tr.span("d", "dispatch"):
            opens = tr.open_categories()
            assert opens.get("device_compute") == 1
            assert opens.get("dispatch") == 1
        release.set()
        t.join(5)
        assert tr.open_categories() == {}

    def test_thread_lifecycle(self):
        tr = S.SpanTracer()
        wd = Watchdog(tr, interval_s=0.01, log=lambda _s: None)
        with wd:
            time.sleep(0.05)
        assert wd.polls >= 1
        assert wd.poll_errors == 0


class TestQueueTracing:
    """Spans through the TPU pull queue: decisions bit-identical with
    tracing on/off, and the decomposition categories all appear."""

    def _drive(self, tracer, spec=0):
        from dmclock_tpu.core.qos import ClientInfo
        from dmclock_tpu.engine.queue import TpuPullPriorityQueue

        q = TpuPullPriorityQueue(
            lambda c: ClientInfo(1.0, 1.0, 0.0), capacity=8,
            speculative_batch=spec, tracer=tracer)
        decs = []
        for t in range(16):
            q.add_request(("r", t), t % 3, time_ns=t * 10 ** 6)
        for t in range(20):
            pr = q.pull_request(now_ns=10 ** 9 + t * 10 ** 6)
            decs.append((pr.type, getattr(pr, "client", None),
                         getattr(pr, "cost", None)))
        return decs

    def test_decisions_bit_identical_and_categories(self):
        tr = S.SpanTracer()
        assert self._drive(None) == self._drive(tr)
        counts = tr.category_counts()
        for cat in ("ingest", "host_prep", "dispatch",
                    "device_compute", "fetch", "drain"):
            assert counts.get(cat, 0) > 0, cat

    def test_speculative_path_traced(self):
        tr = S.SpanTracer()
        assert self._drive(None, spec=4) == self._drive(tr, spec=4)
        assert tr.category_counts().get("dispatch", 0) > 0
        assert tr.category_counts().get("fetch", 0) > 0


class TestGuardedTracing:
    """run_epoch_guarded with a tracer: decisions bit-identical on all
    three epoch engines (the ci.sh tracing gate's in-suite twin)."""

    @pytest.mark.parametrize("engine", ["prefix", "chain", "calendar"])
    def test_digest_identical_with_tracer(self, engine):
        import hashlib

        import jax

        from __graft_entry__ import _preloaded_state
        from dmclock_tpu.robust.guarded import run_epoch_guarded

        def digest(ep):
            h = hashlib.sha256()
            for r in ep.results:
                for name in ("count", "slot", "phase", "cost",
                             "served", "length"):
                    if hasattr(r, name):
                        h.update(np.asarray(
                            jax.device_get(getattr(r, name))
                        ).tobytes())
            return h.hexdigest()

        def run(tracer):
            st = _preloaded_state(256, 8, ring=16)
            return run_epoch_guarded(st, 10 ** 9, engine=engine,
                                     m=2, k=16, tracer=tracer)

        tr = S.SpanTracer()
        ref, traced = run(None), run(tr)
        assert digest(ref) == digest(traced)
        assert ref.count == traced.count
        counts = tr.category_counts()
        # one guarded epoch = one dispatch + one device wait (m
        # batches ride inside the single launch)
        assert counts.get("dispatch", 0) >= 1
        assert counts.get("device_compute", 0) >= 1

    def test_stream_chunk_spans_in_order(self):
        """One guarded stream chunk: the counts' upload, the launch,
        the wait and the readback, each in its own category."""
        from __graft_entry__ import _preloaded_state
        from dmclock_tpu.robust.guarded import run_stream_chunk_guarded

        tr = S.SpanTracer()
        res = run_stream_chunk_guarded(
            _preloaded_state(64, 4, ring=16), 0,
            np.ones((2, 64), np.int32), engine="prefix", epochs=2, m=2,
            k=8, dt_epoch_ns=10 ** 8, waves=2, tracer=tr)
        assert res.stream_fallback == 0
        rows = [(r["name"], r["cat"]) for r in tr.rows()
                if r["name"].startswith("stream.")]
        assert rows == [("stream.upload", "host_prep"),
                        ("stream.dispatch", "dispatch"),
                        ("stream.device_wait", "device_compute"),
                        ("stream.fetch", "fetch")]

    def test_prefix_dispatch_spans_carry_window_width(self):
        """The prefix engine's dispatch spans, stream and round loop,
        carry the ring-window width the scan prefetches and the
        prefetches per epoch; other engines' spans carry neither."""
        from __graft_entry__ import _preloaded_state
        from dmclock_tpu.engine.fastpath import prefix_window_width
        from dmclock_tpu.robust.guarded import (run_epoch_guarded,
                                                run_stream_chunk_guarded)

        m, ring = 16, 16
        w = prefix_window_width(m, ring)
        assert w < m
        tr = S.SpanTracer()
        st = _preloaded_state(64, 4, ring=ring)
        run_stream_chunk_guarded(
            st, 0, np.ones((1, 64), np.int32), engine="prefix", epochs=1,
            m=m, k=8, dt_epoch_ns=10 ** 8, waves=2, tracer=tr)
        run_epoch_guarded(st, 10 ** 9, engine="prefix", m=m, k=8,
                          tracer=tr)
        run_epoch_guarded(st, 10 ** 9, engine="calendar", m=2, k=4,
                          tracer=tr)
        args = {(r["name"], (r["args"] or {}).get("engine")): r["args"]
                for r in tr.rows() if r["cat"] == "dispatch"}
        want = {"window_m": w, "prefetches": m // w}
        assert {k: args["stream.dispatch", "prefix"][k]
                for k in want} == want
        assert {k: args["guarded.dispatch", "prefix"][k]
                for k in want} == want
        assert "window_m" not in args["guarded.dispatch", "calendar"]


class TestSupervisorSpanLog:
    def _job(self, span_log=None):
        from dmclock_tpu.robust.supervisor import EpochJob

        return EpochJob(n=128, depth=8, ring=16, epochs=4, m=2, k=32,
                        ckpt_every=2, span_log=span_log)

    def test_span_log_off_is_bit_identical(self, tmp_path):
        from dmclock_tpu.robust import host_faults as HF
        from dmclock_tpu.robust import supervisor as SV

        ref = SV.run_job(self._job())
        sp = str(tmp_path / "spans.jsonl")
        r1 = SV.run_supervised(self._job(span_log=sp),
                               str(tmp_path / "wd"),
                               HF.zero_host_plan())
        SV.assert_crash_equivalent(r1, ref)
        names = {r["name"] for r in S.load_jsonl(sp)}
        assert {"supervisor.epoch", "supervisor.ingest",
                "supervisor.digest", "supervisor.checkpoint_save",
                "guarded.dispatch",
                "guarded.device_wait"} <= names

    def test_span_stream_survives_kill_and_resume(self, tmp_path):
        from dmclock_tpu.robust import host_faults as HF
        from dmclock_tpu.robust import supervisor as SV

        ref = SV.run_job(self._job())
        sp = str(tmp_path / "spans.jsonl")
        plan = HF.HostFaultPlan(kill_at_decisions=(ref.decisions,))
        r1 = SV.run_supervised(self._job(span_log=sp),
                               str(tmp_path / "wd"), plan)
        SV.assert_crash_equivalent(r1, ref)
        assert r1.restarts == 1
        rows = S.load_jsonl(sp)
        names = [r["name"] for r in rows]
        # the first incarnation's flushed epochs survive AND the
        # second incarnation's resume span is in the stream
        assert names.count("supervisor.resume") == 1
        assert names.count("supervisor.checkpoint_save") >= 2
        # no double counting: replayed epochs appear exactly once
        # (flushes are gated to checkpoint boundaries, so nothing a
        # resume replays was ever flushed by the dead incarnation)
        epochs_seen = [r["args"]["epoch"] for r in rows
                       if r["name"] == "supervisor.epoch"]
        assert sorted(epochs_seen) == sorted(set(epochs_seen))
        # the stream is valid JSONL end to end (load_jsonl validated)
        # and exports to a loadable chrome trace
        out = str(tmp_path / "t.json")
        TE.export_chrome_trace(rows, out)
        json.load(open(out))


class TestClusterTracing:
    def test_run_cluster_rounds_traced_matches_untraced(self):
        import jax.numpy as jnp

        from dmclock_tpu.core.timebase import rate_to_inv_ns
        from dmclock_tpu.parallel import cluster as CL

        S_, C, T, K = 2, 4, 3, 8
        mesh = CL.make_mesh(2)

        def fresh():
            cl = CL.init_cluster(S_, C)
            return CL.shard_cluster(CL.install_clients(
                cl,
                jnp.asarray([rate_to_inv_ns(10.0)] * C, jnp.int64),
                jnp.asarray([rate_to_inv_ns(1.0)] * C, jnp.int64),
                jnp.asarray([0] * C, jnp.int64)), mesh)

        arrivals = np.ones((T, S_, C), dtype=np.int32)
        _, seq0 = CL.run_cluster_rounds(
            fresh(), arrivals, 1, mesh, decisions_per_step=K,
            advance_ns=10 ** 8)
        tr = S.SpanTracer()
        _, seq1 = CL.run_cluster_rounds(
            fresh(), arrivals, 1, mesh, decisions_per_step=K,
            advance_ns=10 ** 8, tracer=tr)
        for a, b in zip(seq0, seq1):
            assert np.array_equal(np.asarray(a.type),
                                  np.asarray(b.type))
            assert np.array_equal(np.asarray(a.slot),
                                  np.asarray(b.slot))
        assert tr.category_counts()["dispatch"] == T
        assert tr.category_counts()["fetch"] == T

    def test_run_with_plan_traced_digest_identical(self):
        import jax.numpy as jnp

        from dmclock_tpu.core.timebase import rate_to_inv_ns
        from dmclock_tpu.parallel import cluster as CL
        from dmclock_tpu.robust import cluster as RC

        S_, C, T, K = 2, 4, 3, 8
        mesh = CL.make_mesh(2)

        def fresh():
            cl = CL.init_cluster(S_, C)
            cl = CL.install_clients(
                cl,
                jnp.asarray([rate_to_inv_ns(10.0)] * C, jnp.int64),
                jnp.asarray([rate_to_inv_ns(1.0)] * C, jnp.int64),
                jnp.asarray([0] * C, jnp.int64))
            return RC.shard_robust(
                RC.init_robust(CL.shard_cluster(cl, mesh)), mesh)

        arrivals = np.ones((T, S_, C), dtype=np.int32)
        _, seq0 = RC.run_with_plan(fresh(), arrivals, 1, mesh, None,
                                   decisions_per_step=K,
                                   advance_ns=10 ** 8)
        tr = S.SpanTracer()
        _, seq1 = RC.run_with_plan(fresh(), arrivals, 1, mesh, None,
                                   decisions_per_step=K,
                                   advance_ns=10 ** 8, tracer=tr)
        assert RC.decision_digest(seq0) == RC.decision_digest(seq1)
        assert tr.category_counts()["dispatch"] == T
