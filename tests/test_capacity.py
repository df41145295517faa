"""Capacity plane tests (docs/OBSERVABILITY.md "Capacity plane"):

- the compile/retrace observatory: instrumented jit caches compile
  once per signature, dispatch bit-identical results with the plane
  on/off, attribute retraces to the arg-signature diff that caused
  them, survive AOT-executable rejections by falling back to plain
  dispatch, and emit ``compile``-category spans + ``dmclock_compile_*``
  families;
- the HBM ledger + planner: exact linearity, plan_capacity round-trip
  (planned N fits, N+eps refuses), projection within 10% of the real
  compiled program's ``memory_analysis()`` argument bytes;
- roofline classification rules (dispatch-/compute-/memory-bound);
- the watchdog's retrace-storm warning: deterministic ``poll_once``
  coverage — fires once per episode, re-arms on a quiet window, and
  never fires on the legitimate first-compiles of an AOT pre-compile
  loop (the PR-8 chunk-length pattern);
- the doc-drift gate: every Prometheus family the code registers
  matches a docs/OBSERVABILITY.md metric-family-index row, and every
  index row matches something in the code.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.obs import capacity as obscap
from dmclock_tpu.obs import compile_plane as cplane
from dmclock_tpu.obs import spans as obsspans
from dmclock_tpu.obs.registry import MetricsRegistry
from dmclock_tpu.obs.watchdog import Watchdog

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def plane():
    pl = cplane.plane()
    pl.reset()
    pl.enable(True)
    tracer0 = pl.tracer
    pl.set_tracer(None)
    yield pl
    pl.reset()
    pl.enable(True)
    pl.set_tracer(tracer0)


class TestCompilePlane:
    def test_compiles_once_per_signature(self, plane):
        j = cplane.instrumented_jit(lambda a, b: a * b + 1,
                                    cache="t", entry=("e", 1))
        a = jnp.arange(8, dtype=jnp.int64)
        r1 = j(a, jnp.int64(2))
        r2 = j(a, jnp.int64(5))       # same signature: no new compile
        assert np.array_equal(np.asarray(r1),
                              np.asarray(a) * 2 + 1)
        assert np.array_equal(np.asarray(r2),
                              np.asarray(a) * 5 + 1)
        t = plane.totals()
        assert t["compiles"] == 1 and t["retraces"] == 0

    def test_retrace_records_signature_diff(self, plane):
        j = cplane.instrumented_jit(lambda a: a + 1, cache="t",
                                    entry="e")
        j(jnp.arange(8, dtype=jnp.int64))
        j(jnp.arange(16, dtype=jnp.int64))
        t = plane.totals()
        assert t["compiles"] == 2 and t["retraces"] == 1
        (e,) = plane.entries()
        assert e["retraces"] == 1
        assert e["last_retrace_diff"], "retrace must carry its diff"
        assert "(8,)" in e["last_retrace_diff"][0]
        assert "(16,)" in e["last_retrace_diff"][0]
        assert len(plane.retrace_events()) == 1

    def test_results_match_plain_jit_and_plane_off(self, plane):
        def fn(s, t):
            return {"x": s["x"] * t, "y": s["y"].sum()}

        j = cplane.instrumented_jit(fn, cache="t", entry="e")
        args = ({"x": jnp.arange(6, dtype=jnp.int64),
                 "y": jnp.ones((3,), jnp.float64)}, jnp.int64(3))
        on = j(*args)
        plane.enable(False)
        off = j(*args)
        ref = jax.jit(fn)(*args)
        for k in ref:
            assert np.array_equal(np.asarray(on[k]),
                                  np.asarray(ref[k]))
            assert np.array_equal(np.asarray(off[k]),
                                  np.asarray(ref[k]))

    def test_cost_and_memory_analysis_recorded(self, plane):
        j = cplane.instrumented_jit(lambda a: (a * 2).sum(),
                                    cache="t", entry="e")
        j(jnp.arange(64, dtype=jnp.int64))
        (e,) = plane.entries()
        assert e["compile_ms"] > 0 and e["lower_ms"] > 0
        assert e["cost_analysis"].get("flops", 0) > 0
        assert e["memory_analysis"].get("argument_bytes") == 64 * 8

    def test_dispatch_fallback_on_rejected_executable(self, plane):
        j = cplane.instrumented_jit(lambda a: a + 1, cache="t",
                                    entry="e")
        a8 = jnp.arange(8, dtype=jnp.int64)
        a16 = jnp.arange(16, dtype=jnp.int64)
        j(a8)
        # poison: route a16's signature at a8's executable -- the AOT
        # call must reject (TypeError) and the wrapper must fall back
        # to plain jit dispatch with the CORRECT result, permanently
        sig16 = cplane._signature((a16,), {})
        j._compiled[sig16] = j._compiled[cplane._signature((a8,), {})]
        out = j(a16)
        assert np.array_equal(np.asarray(out), np.arange(16) + 1)
        assert plane.totals()["dispatch_fallbacks"] == 1
        out2 = j(a16)   # permanently routed; no second fallback count
        assert np.array_equal(np.asarray(out2), np.arange(16) + 1)
        assert plane.totals()["dispatch_fallbacks"] == 1

    def test_tracer_args_route_to_plain_jit(self, plane):
        inner = cplane.instrumented_jit(lambda a: a * 2, cache="t",
                                        entry="inner")

        @jax.jit
        def outer(a):
            return inner(a) + 1     # traced arg: must inline cleanly

        out = outer(jnp.arange(4, dtype=jnp.int64))
        assert np.array_equal(np.asarray(out), np.arange(4) * 2 + 1)

    def test_compile_spans_ride_attached_tracer(self, plane):
        tr = obsspans.SpanTracer()
        plane.set_tracer(tr)
        j = cplane.instrumented_jit(lambda a: a + 1, cache="spanned",
                                    entry="e")
        j(jnp.arange(4, dtype=jnp.int64))
        cats = tr.category_counts()
        assert cats.get("compile", 0) >= 1
        names = {n for (n, c) in tr.name_stats() if c == "compile"}
        assert "compile.spanned" in names

    def test_clear_compiled_recompiles(self, plane):
        j = cplane.instrumented_jit(lambda a: a + 1, cache="t",
                                    entry="e")
        a = jnp.arange(4, dtype=jnp.int64)
        j(a)
        cplane.clear_compiled()
        j(a)
        t = plane.totals()
        assert t["compiles"] == 2   # re-lowered after the clear

    def test_aot_record(self, plane):
        comp = cplane.aot_record(
            "bench.test", ("e", 1), jax.jit(lambda a: a * 3),
            jnp.arange(8, dtype=jnp.int64))
        out = comp(jnp.arange(8, dtype=jnp.int64))
        assert np.array_equal(np.asarray(out), np.arange(8) * 3)
        (e,) = plane.entries()
        assert e["cache"] == "bench.test" and e["compiles"] == 1
        # same entry compiled again = a retrace (bench chunk lengths
        # are DIFFERENT entries, so the pre-compile loop records none)
        cplane.aot_record("bench.test", ("e", 1),
                          jax.jit(lambda a: a * 3),
                          jnp.arange(8, dtype=jnp.int64))
        assert plane.totals()["retraces"] == 1

    def test_publish_compile_metrics(self, plane):
        j = cplane.instrumented_jit(lambda a: a + 1, cache="fam",
                                    entry="e")
        j(jnp.arange(4, dtype=jnp.int64))
        reg = MetricsRegistry()
        cplane.publish_compile_metrics(reg, plane)
        text = reg.prometheus()
        for fam in ("dmclock_compile_events_total",
                    "dmclock_compile_retraces_total",
                    "dmclock_compile_ms_total",
                    "dmclock_compile_lower_ms_total",
                    "dmclock_compile_cache_entries",
                    "dmclock_compile_flops",
                    "dmclock_compile_bytes_accessed",
                    "dmclock_compile_hbm_bytes"):
            assert fam in text, fam
        assert 'cache="fam"' in text

    def test_guarded_epoch_digest_identical_plane_on_off(self, plane):
        from __graft_entry__ import _preloaded_state
        from dmclock_tpu.robust.guarded import run_epoch_guarded

        def digest(ep):
            import hashlib
            h = hashlib.sha256()
            for r in ep.results:
                for name in ("count", "slot", "phase", "cost"):
                    if hasattr(r, name):
                        h.update(np.asarray(jax.device_get(
                            getattr(r, name))).tobytes())
            return h.hexdigest()

        digs = {}
        for on in (True, False):
            plane.enable(on)
            st = _preloaded_state(256, 6, ring=8)
            ep = run_epoch_guarded(st, 10 ** 9, engine="prefix", m=2,
                                   k=32)
            digs[on] = digest(ep)
        assert digs[True] == digs[False]


class TestSupervisedCompileRecords:
    def test_compile_spans_ride_span_log_and_crash_gate_holds(
            self, plane, tmp_path):
        """The supervisor attaches its per-incarnation tracer to the
        compile plane, so compile records flush with the span_log at
        checkpoint boundaries (the rotation checkpoints' durability
        window) -- and the PR-5 crash-equivalence gate is unaffected
        by the plane being on."""
        from dmclock_tpu.obs.spans import load_jsonl
        from dmclock_tpu.robust import host_faults as HF
        from dmclock_tpu.robust import supervisor as SV

        job = SV.EpochJob(engine="prefix", n=96, depth=5, ring=8,
                          epochs=4, m=2, k=16, seed=7,
                          arrival_lam=1.0, waves=3, ckpt_every=2,
                          span_log=str(tmp_path / "spans.jsonl"))
        ref = SV.run_job(dataclasses_replace_no_log(job))
        # drop the executables the reference run compiled, so the
        # supervised incarnation re-compiles (and its span stream
        # carries the compile records)
        cplane.clear_compiled()
        sup = SV.run_supervised(job, str(tmp_path / "wd"),
                                HF.zero_host_plan())
        SV.assert_crash_equivalent(sup, ref)
        rows = load_jsonl(job.span_log)
        comp = [r for r in rows if r["cat"] == "compile"]
        assert comp, "compile spans must ride the span_log stream"
        assert any(r["name"].startswith("compile.") for r in comp)
        # the record instants carry the compile payload
        recs = [r for r in comp if r["name"].endswith(".record")]
        assert recs and "compile_ms" in (recs[0].get("args") or {})


def dataclasses_replace_no_log(job):
    import dataclasses

    return dataclasses.replace(job, span_log=None)


class TestLedgerAndPlanner:
    CFG = dict(ring=16, engine="prefix", m=2, k=64, telemetry=True,
               slo=True, flight_records=32)

    def test_ledger_matches_real_state_bytes(self):
        from dmclock_tpu.engine.state import init_state

        led = obscap.hbm_ledger(128, ring=16)
        st = init_state(128, 16)
        real = sum(np.asarray(x).nbytes
                   for x in jax.tree_util.tree_leaves(st))
        assert led["client_state"] + led["rings"] == real

    def test_model_linearity_exact(self):
        model = obscap.capacity_model(**self.CFG)
        direct = obscap.hbm_ledger(4096, **self.CFG)
        assert model.ledger(4096) == direct

    def test_plan_capacity_round_trip(self):
        budget = 1 << 30
        plan = obscap.plan_capacity(budget, **self.CFG)
        n = plan["max_clients"]
        assert n > 0
        assert obscap.fits(n, budget, **self.CFG)
        assert not obscap.fits(n + 1024, budget, **self.CFG)
        assert plan["projected_bytes"] <= plan["usable_bytes"]

    def test_stream_chunk_multiplies_outputs(self):
        l1 = obscap.hbm_ledger(512, **self.CFG)
        l8 = obscap.hbm_ledger(512, stream_chunk=8, **self.CFG)
        assert l8["epoch_outputs"] == 8 * l1["epoch_outputs"]
        for k in l1:
            if k != "epoch_outputs":
                assert l8[k] == l1[k]

    def test_projection_within_10pct_of_memory_analysis(self, plane):
        """The acceptance gate's small twin (ci.sh runs the cfg4
        shape): the ledger's resident-argument projection vs the real
        compiled epoch program's memory_analysis argument bytes."""
        import functools

        from __graft_entry__ import _preloaded_state
        from dmclock_tpu.engine import fastpath
        from dmclock_tpu.obs import histograms as obshist
        from dmclock_tpu.obs import slo as obsslo

        n, ring, m, k = 512, 16, 2, 64
        st = _preloaded_state(n, 6, ring=ring)
        comp = cplane.aot_record(
            "test.capacity", "proj-gate",
            jax.jit(functools.partial(
                fastpath.scan_prefix_epoch, m=m, k=k,
                anticipation_ns=0, with_metrics=True)),
            st, jnp.int64(0), hists=obshist.hist_zero(),
            ledger=obshist.ledger_zero(n), slo=obsslo.window_zero(n))
        mem = cplane.memory_analysis_dict(comp)
        assert mem.get("argument_bytes", 0) > 0
        led = obscap.hbm_ledger(n, ring=ring, telemetry=True,
                                slo=True)
        projected_args = sum(led.values())
        measured = mem["argument_bytes"]
        assert abs(projected_args - measured) <= 0.10 * measured, \
            (projected_args, measured)

    def test_device_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES", "987654")
        assert obscap.device_hbm_budget() == 987654
        # 0 = detection disabled (not a zero-byte budget that would
        # gate every workload)
        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES", "0")
        assert obscap.device_hbm_budget() is None
        monkeypatch.delenv("DMCLOCK_HBM_BUDGET_BYTES")
        # cpu backend: no memory_stats -> None (host RAM is not HBM)
        assert obscap.device_hbm_budget() is None


class TestRoofline:
    PK = dict(peak_flops=1e12, peak_bytes_per_s=1e11)  # balance 10

    def test_dispatch_bound_wins(self):
        out = obscap.classify(flops=1e12, bytes_accessed=1e9,
                              device_time_s=0.001,
                              dispatch_time_s=0.01, **self.PK)
        assert out["bound_class"] == "dispatch_bound"
        assert out["dispatch_share"] > 0.9

    def test_memory_vs_compute_ridge(self):
        lo = obscap.classify(flops=1e9, bytes_accessed=1e9, **self.PK)
        hi = obscap.classify(flops=1e11, bytes_accessed=1e9,
                             **self.PK)
        assert lo["bound_class"] == "memory_bound"
        assert hi["bound_class"] == "compute_bound"
        assert lo["arithmetic_intensity"] == 1.0

    def test_unknown_without_cost_data(self):
        out = obscap.classify(flops=0.0, bytes_accessed=0.0,
                              **self.PK)
        assert out["bound_class"] == "unknown"

    def test_peaks_keyed_by_device_kind(self):
        """A v5e reports device_kind "TPU v5 lite" and must get the
        v5e peaks; a kind not in the table is an error, not a
        made-up default."""
        class Dev:
            def __init__(self, kind):
                self.device_kind = kind

        v5e = obscap.device_peaks(Dev("TPU v5 lite"))
        assert (v5e["peak_flops"], v5e["peak_bytes_per_s"]) == \
            (197e12, 819e9)
        for kind in ("TPU v99", "v5e", "TPU v5 lite "):
            with pytest.raises(KeyError, match="no roofline peaks"):
                obscap.device_peaks(Dev(kind))
        assert obscap.device_peaks()["label"] == \
            jax.devices()[0].device_kind

    def test_classify_bench_row_joins_spans(self):
        row = {"cost_analysis": {"flops": 1e9,
                                 "bytes_accessed": 1e9},
               "spans": {"dispatch_ms_per_launch": 20.0,
                         "device_ms_per_launch": 1.0}}
        out = obscap.classify_bench_row(row, peaks=self.PK)
        assert out["bound_class"] == "dispatch_bound"
        row["spans"]["dispatch_ms_per_launch"] = 0.1
        out = obscap.classify_bench_row(row, peaks=self.PK)
        assert out["bound_class"] == "memory_bound"


class TestRetraceStormWatchdog:
    def _setup(self, k=3, window_s=100.0):
        clock = {"t": 1_000_000_000}

        def clock_ns():
            return clock["t"]

        pl = cplane.CompilePlane(clock_ns=clock_ns)
        tr = obsspans.SpanTracer(clock_ns=clock_ns)
        wd = Watchdog(tr, compile_plane=pl, retrace_storm_k=k,
                      retrace_window_s=window_s, stall_after_s=1e9,
                      log=lambda _line: None, clock_ns=clock_ns)
        return clock, pl, wd

    def _retrace(self, pl, entry="queue:('run', 1)"):
        # a compile event on an entry that already compiled = retrace
        pl.record_compile(entry.split(":")[0], entry.split(":")[1],
                          lower_ns=1, compile_ns=1, cost={}, hbm={})

    def test_fires_once_per_episode_and_rearms(self):
        clock, pl, wd = self._setup(k=3, window_s=100.0)
        for _ in range(4):          # 1 first compile + 3 retraces
            self._retrace(pl)
        warns = wd.poll_once()
        assert [w["kind"] for w in warns] == ["retrace_storm"]
        assert warns[0]["retraces"] == 3
        # same storm still in window: once per episode, no repeat
        assert wd.poll_once() == []
        # quiet window re-arms ...
        clock["t"] += int(200e9)
        assert wd.poll_once() == []
        # ... and a NEW storm fires again
        for _ in range(3):
            self._retrace(pl)
        warns = wd.poll_once()
        assert [w["kind"] for w in warns] == ["retrace_storm"]

    def test_distinct_entries_below_threshold_never_fire(self):
        clock, pl, wd = self._setup(k=3)
        # the PR-8 AOT pre-compile pattern: one FIRST compile per
        # chunk length -- distinct entries, zero retraces
        for c in (1, 2, 4, 8, 16, 32):
            pl.record_compile("bench.chunk", f"(cfg, {c})",
                              lower_ns=1, compile_ns=1, cost={},
                              hbm={})
        assert pl.totals()["retraces"] == 0
        assert wd.poll_once() == []
        # and 2 retraces each on two DIFFERENT entries stay below k=3
        for entry in ("queue:a", "queue:b"):
            self._retrace(pl, entry)
            self._retrace(pl, entry)
            self._retrace(pl, entry)  # 3rd compile = 2nd retrace
        assert wd.poll_once() == []

    def test_real_aot_precompile_loop_never_warns(self):
        """End-to-end twin of the bench's chunk pre-compile: real
        jits, one entry per chunk length, watchdog polling after."""
        clock, pl, wd = self._setup(k=2)
        for c in (1, 2, 4):
            compiled = jax.jit(lambda a, c=c: a * c).lower(
                jnp.arange(4, dtype=jnp.int64)).compile()
            pl.record_compile("bench.chunk", f"(shape, {c})",
                              lower_ns=1, compile_ns=1,
                              cost=cplane.cost_analysis_dict(compiled),
                              hbm=cplane.memory_analysis_dict(
                                  compiled))
        assert wd.poll_once() == []
        assert pl.totals()["compiles"] == 3
        assert pl.totals()["retraces"] == 0

    def test_watchdog_without_plane_unaffected(self):
        tr = obsspans.SpanTracer()
        wd = Watchdog(tr, log=lambda _line: None)
        assert wd.poll_once() == []


class TestDocDrift:
    """The metric-family index in docs/OBSERVABILITY.md is a contract:
    families the code registers must appear in it, and index rows must
    point at something real."""

    @staticmethod
    def _doc_patterns():
        text = (REPO / "docs" / "OBSERVABILITY.md").read_text()
        start = text.index("## Metric-family index")
        end = text.index("\n## ", start + 10)
        pats = []
        for tok in re.findall(r"`([A-Za-z0-9_*]+)`", text[start:end]):
            if tok.startswith(("dmclock_", "sim_")):
                pats.append(tok)
        assert pats, "metric-family index table not found"
        return pats

    @staticmethod
    def _matches(name: str, pat: str) -> bool:
        if "*" in pat:
            prefix = pat.split("*", 1)[0]
            return name.startswith(prefix) or prefix.startswith(name)
        return name == pat or name.startswith(pat) \
            or pat.startswith(name)

    def _registered_names(self):
        """Exercise every cheaply-runnable publisher into one registry
        and return the family names it holds."""
        from dmclock_tpu.control import Controller, as_spec
        from dmclock_tpu.lifecycle import make_spec
        from dmclock_tpu.lifecycle.placement import PlacementMap
        from dmclock_tpu.lifecycle.plane import LifecyclePlane
        from dmclock_tpu.obs import device as obsdev
        from dmclock_tpu.obs import histograms as obshist
        from dmclock_tpu.obs import provenance as obsprov
        from dmclock_tpu.obs import slo as obsslo
        from dmclock_tpu.obs.alerts import SloEvaluator
        from dmclock_tpu.obs.registry import publish_span_gauges

        reg = MetricsRegistry()
        obsprov.publish_provenance(reg, obsprov.prov_init(2))
        obsprov.publish_shard_pressure(
            reg, np.zeros((1, obsprov.PRESS_FIELDS), dtype=np.int64),
            np.zeros(obsprov.PRESS_FIELDS, dtype=np.int64))
        obsprov.StarvationMonitor(10 ** 9, registry=reg,
                                  log=lambda _l: None)
        obsdev.publish(reg, np.zeros(obsdev.NUM_METRICS,
                                     dtype=np.int64))
        obsdev.publish_shard_faults(
            reg, np.zeros((2, 3), dtype=np.int64))
        obshist.publish_hists(reg, obshist.hist_zero())
        obshist.publish_ledger(reg, np.zeros((4, obshist.LED_COLS),
                                             dtype=np.int64))
        obsslo.publish_shard_windows(
            reg, np.zeros((2, 2, obsslo.W_FIELDS), dtype=np.int64))
        publish_span_gauges(reg, {"dispatch_ms_per_launch": 1.0,
                                  "device_ms_per_launch": 1.0,
                                  "host_overhead_frac": 0.1})
        Watchdog(obsspans.SpanTracer(), registry=reg,
                 log=lambda _l: None)
        SloEvaluator(obsslo.SloPlane(2, dt_epoch_ns=10 ** 8),
                     registry=reg, log=lambda _l: None)
        pl = cplane.CompilePlane()
        pl.record_compile("t", "e", lower_ns=1, compile_ns=1,
                          cost={"flops": 1.0, "bytes_accessed": 1.0},
                          hbm={"total_bytes": 1})
        cplane.publish_compile_metrics(reg, pl)
        obscap.publish_capacity_metrics(reg, projected_bytes=1,
                                        budget_bytes=1, max_clients=1,
                                        workload="t")
        LifecyclePlane(make_spec("flash_crowd", total_ids=8)) \
            .publish(reg)
        PlacementMap(2, 8).publish(reg)
        Controller(as_spec(True), n=4, ring=4, registry=reg)
        from dmclock_tpu.obs import rpc as obsrpc
        obsrpc.publish_rpc(reg, {"queue_depth": 0, "connections": 0,
                                 "device_pressure": False,
                                 "shard_rx": {"0": 0},
                                 "counters": {}})
        obsrpc.publish_rpc_latency(reg,
                                   obsrpc.latency_summary([10 ** 6]))
        return sorted({m.name for m in reg.metrics()})

    @staticmethod
    def _static_names():
        """Family-name literals at registration call sites
        (.counter/.gauge/.histogram/.timer first args), normalized to
        prefixes at the first f-string hole."""
        rx = re.compile(
            r"\.(?:counter|gauge|histogram|timer)\(\s*f?[\"']"
            r"((?:dmclock|sim)_[A-Za-z0-9_{}]*)", re.S)
        names = set()
        files = list((REPO / "dmclock_tpu").rglob("*.py")) \
            + [REPO / "bench.py"] \
            + list((REPO / "scripts").glob("*.py"))
        for p in files:
            for m in rx.finditer(p.read_text()):
                name = m.group(1).split("{", 1)[0].rstrip("_")
                if name.count("_") >= 1:
                    names.add(name)
        assert names, "no registration sites found"
        return sorted(names)

    def test_registered_families_are_documented(self):
        pats = self._doc_patterns()
        missing = [n for n in self._registered_names()
                   if not any(self._matches(n, p) for p in pats)]
        assert not missing, \
            (f"families registered by code but absent from the "
             f"docs/OBSERVABILITY.md metric-family index: {missing}")

    def test_static_registration_sites_are_documented(self):
        pats = self._doc_patterns()
        missing = [n for n in self._static_names()
                   if not any(self._matches(n, p) for p in pats)]
        assert not missing, \
            (f"registration-site names absent from the metric-family "
             f"index: {missing}")

    def test_documented_families_exist_in_code(self):
        registered = self._registered_names()
        static = self._static_names()
        src = "\n".join(p.read_text() for p in
                        list((REPO / "dmclock_tpu").rglob("*.py"))
                        + [REPO / "bench.py"]
                        + list((REPO / "scripts").glob("*.py")))
        rotted = []
        for pat in self._doc_patterns():
            prefix = pat.split("*", 1)[0].rstrip("_")
            hit = any(self._matches(n, pat)
                      for n in registered + static) \
                or prefix in src
            if not hit:
                rotted.append(pat)
        assert not rotted, \
            (f"metric-family index rows pointing at nothing in the "
             f"code: {rotted}")

    def test_new_capacity_families_bidirectional(self):
        """The strong form for the families this plane adds: exactly
        what publish_* registers must be indexed, and every indexed
        dmclock_compile_*/dmclock_capacity_* token must be
        registered."""
        reg = MetricsRegistry()
        pl = cplane.CompilePlane()
        pl.record_compile("t", "e", lower_ns=1, compile_ns=1,
                          cost={"flops": 1.0, "bytes_accessed": 1.0},
                          hbm={"total_bytes": 1})
        cplane.publish_compile_metrics(reg, pl)
        obscap.publish_capacity_metrics(reg, projected_bytes=1,
                                        budget_bytes=1, max_clients=1,
                                        workload="t")
        names = {m.name for m in reg.metrics()}
        pats = self._doc_patterns()
        for n in names:
            assert any(self._matches(n, p) for p in pats), n
        doc_new = [p for p in pats
                   if p.startswith(("dmclock_compile_",
                                    "dmclock_capacity_"))
                   and "*" not in p]
        for p in doc_new:
            assert p in names, \
                f"indexed family {p} is not registered by the " \
                "capacity-plane publishers"


class TestBenchCapacityGate:
    def test_gate_skips_over_budget_and_passes_under(self,
                                                     monkeypatch):
        import bench

        cfg = dict(n=4096, ring=64, engine="prefix", m=4, k=256,
                   telemetry=True, slo=True)
        need = obscap.projected_hbm(4096, **{k: v for k, v in
                                             cfg.items() if k != "n"})
        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES",
                           str(int(need * 0.5)))
        row = bench._capacity_gate(cfg, engine_loop="stream")
        assert row is not None and row["capacity_skipped"]
        assert row["dps"] == 0.0
        assert row["engine_loop"] == "stream"
        assert row["projected_hbm_bytes"] > row["hbm_budget_bytes"] \
            * 0.9
        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES",
                           str(int(need * 10)))
        assert bench._capacity_gate(cfg) is None

    def test_gate_never_raises_on_garbage(self, monkeypatch):
        import bench

        monkeypatch.setenv("DMCLOCK_HBM_BUDGET_BYTES", "1000000")
        assert bench._capacity_gate({"n": 64, "engine": "nonsense",
                                     "bogus_knob": 1}) is None
