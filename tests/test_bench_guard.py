"""Unit tests for the drift-aware benchmark regression guard
(scripts/bench_guard.py): history medians, same-device filtering, the
tolerance floor, and the not-enough-history pass."""

import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
spec = importlib.util.spec_from_file_location(
    "bench_guard", REPO / "scripts" / "bench_guard.py")
bg = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bg)


def write_history(tmp_path, rows):
    h = tmp_path / "history"
    h.mkdir()
    for i, (device, dps) in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": device,
             "workloads": {"serve": {"dps": dps}}}))
    return h


def run_guard(monkeypatch, capsys, hist, argv=()):
    monkeypatch.setattr(bg, "HISTORY", hist)
    monkeypatch.setattr(sys, "argv", ["bench_guard.py", *argv])
    rc = bg.main()
    return rc, capsys.readouterr().out


def test_no_history_passes(monkeypatch, capsys, tmp_path):
    rc, out = run_guard(monkeypatch, capsys, tmp_path / "none")
    assert rc == 0
    assert "no history" in out


def test_within_drift_passes(monkeypatch, capsys, tmp_path):
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 35e6),
                                    ("tpu0", 45e6), ("tpu0", 25e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0 and "OK" in out


def test_big_drop_fails(monkeypatch, capsys, tmp_path):
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 35e6),
                                    ("tpu0", 45e6), ("tpu0", 10e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out


def test_device_change_not_compared(monkeypatch, capsys, tmp_path):
    # a 4x drop on a DIFFERENT device must not read as a regression
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 45e6),
                                    ("tpu1", 10e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "not judged" in out


def test_fallback_newest_annotated_not_judged(monkeypatch, capsys,
                                              tmp_path):
    # a backend-fallback (cpu) session must never read as a regression
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 45e6)])
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "cpu", "device": "CpuDevice(id=0)",
         "fallback": True, "backend_error": "RuntimeError: backend",
         "workloads": {"serve": {"dps": 0.2e6}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "backend-fallback" in out and "not judged" in out


def test_fallback_prior_excluded_from_medians(monkeypatch, capsys,
                                              tmp_path):
    # fallback records in the prior set must not drag the median down
    # and mask a real regression
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 44e6)])
    (hist / "bench_1500.json").write_text(json.dumps(
        {"platform": "cpu", "device": "tpu0", "fallback": True,
         "workloads": {"serve": {"dps": 0.2e6}}}))
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "tpu", "device": "tpu0",
         "workloads": {"serve": {"dps": 10e6}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out
    assert "excluded from medians" in out


def write_history_tard(tmp_path, rows):
    """rows = [(dps, p99_tardiness_ns), ...] on one device."""
    h = tmp_path / "history"
    h.mkdir()
    for i, (dps, p99) in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"cfg4": {"dps": dps,
                                    "tardiness_p99_ns": p99}}}))
    return h


def test_tardiness_series_ok_when_stable(monkeypatch, capsys,
                                         tmp_path):
    hist = write_history_tard(tmp_path, [(40e6, 1e6), (42e6, 2e6),
                                         (41e6, 1.5e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "p99 tardiness" in out and "OK" in out


def test_tardiness_regression_warns_but_passes(monkeypatch, capsys,
                                               tmp_path):
    # tail QoS regressed 10x while throughput held: warn-only (the
    # log2 octaves and calibration shifts make a hard gate flap), and
    # the throughput verdict stays the exit code
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_tard(tmp_path,
                                           [(40e6, 1e6), (42e6, 2e6),
                                            (41e6, 15e6)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING p99 tardiness" in cap.err
    assert "tail QoS regressed" in cap.err


def test_tardiness_not_judged_without_history(monkeypatch, capsys,
                                              tmp_path):
    # records predating the telemetry plane carry no tardiness column
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 42e6)])
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "tpu", "device": "tpu0",
         "workloads": {"serve": {"dps": 41e6,
                                 "tardiness_p99_ns": 3e6}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "p99 tardiness" in out and "not judged" in out


def write_history_dispatch(tmp_path, rows):
    """rows = [(dps, dispatch_ms_per_launch), ...] on one device."""
    h = tmp_path / "history"
    h.mkdir()
    for i, (dps, disp) in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"cfg4": {
                 "dps": dps, "dispatch_ms_per_launch": disp}}}))
    return h


def test_dispatch_series_ok_when_stable(monkeypatch, capsys,
                                        tmp_path):
    hist = write_history_dispatch(tmp_path, [(40e6, 17.0), (42e6, 16.0),
                                             (41e6, 18.5)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "dispatch" in out and "OK" in out


def test_dispatch_regression_warns_but_passes(monkeypatch, capsys,
                                              tmp_path):
    # the per-launch dispatch tax tripled while dec/s held (the chains
    # amortize it): warn-only, throughput stays the exit code
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_dispatch(
                            tmp_path, [(40e6, 17.0), (42e6, 16.0),
                                       (41e6, 55.0)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING dispatch" in cap.err
    assert "dispatch tax regressed" in cap.err


def test_dispatch_submillisecond_median_floored(monkeypatch, capsys,
                                                tmp_path):
    # cpu boxes measure ~µs dispatch; the 1ms floor keeps jitter from
    # reading as a 2x regression
    hist = write_history_dispatch(tmp_path, [(40e6, 0.01), (42e6, 0.02),
                                             (41e6, 0.9)])
    rc, _ = run_guard(monkeypatch, capsys, hist)
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING dispatch" not in cap.err


def test_dispatch_not_judged_without_history(monkeypatch, capsys,
                                             tmp_path):
    # records predating --spans carry no dispatch column
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 42e6)])
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "tpu", "device": "tpu0",
         "workloads": {"serve": {"dps": 41e6,
                                 "dispatch_ms_per_launch": 17.0}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "dispatch" in out and "not judged" in out


def test_tolerance_flag(monkeypatch, capsys, tmp_path):
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 40e6),
                                    ("tpu0", 15e6)])
    rc, _ = run_guard(monkeypatch, capsys, hist)
    assert rc == 1               # 15M < 40M/2 at the default 2x
    rc2, _ = run_guard(monkeypatch, capsys, hist,
                       argv=("--tolerance", "3.0"))
    assert rc2 == 0              # 15M >= 40M/3


def write_history_rows(tmp_path, rows):
    """History records with caller-supplied workload dicts (engine_loop
    / select_impl tags included verbatim)."""
    h = tmp_path / "history"
    h.mkdir()
    for i, wl in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0", "workloads": wl}))
    return h


def test_stream_never_compared_against_round_medians(monkeypatch,
                                                     capsys,
                                                     tmp_path):
    # engine_loop splits the series even under a COLLIDING workload
    # key: a stream session's rates (one launch per chunk) must never
    # be judged against round medians -- here the stream newest is 8x
    # below the round median and must read "not judged", not
    # REGRESSION
    hist = write_history_rows(tmp_path, [
        {"cfg4": {"dps": 40e6}},
        {"cfg4": {"dps": 44e6, "engine_loop": "round"}},
        {"cfg4": {"dps": 5e6, "engine_loop": "stream"}},
    ])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "cfg4[stream]" in out and "not judged" in out


def test_stream_series_judged_against_its_own_history(monkeypatch,
                                                      capsys,
                                                      tmp_path):
    # with enough stream records the stream series is a first-class
    # regression gate of its own
    hist = write_history_rows(tmp_path, [
        {"cfg4_stream": {"dps": 80e6, "engine_loop": "stream"}},
        {"cfg4_stream": {"dps": 90e6, "engine_loop": "stream"}},
        {"cfg4_stream": {"dps": 10e6, "engine_loop": "stream"}},
    ])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out
    assert "cfg4_stream" in out and "[stream]" not in out  # no double tag


def test_round_medians_unpolluted_by_stream_records(monkeypatch,
                                                    capsys, tmp_path):
    # two same-key stream records at 25x the round rate would lift a
    # polluted median past the newest round session's floor; the
    # engine_loop filter keeps them out, so the round session passes
    hist = write_history_rows(tmp_path, [
        {"cfg4": {"dps": 20e6}},
        {"cfg4": {"dps": 22e6, "engine_loop": "round"}},
        {"cfg4": {"dps": 500e6, "engine_loop": "stream"}},
        {"cfg4": {"dps": 500e6, "engine_loop": "stream"}},
        {"cfg4": {"dps": 12e6, "engine_loop": "round"}},
    ])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0 and "OK" in out


def test_decisions_per_launch_printed(monkeypatch, capsys, tmp_path):
    hist = write_history_rows(tmp_path, [
        {"cfg4_stream": {"dps": 80e6, "engine_loop": "stream",
                         "decisions_per_launch": 4096.0}},
        {"cfg4_stream": {"dps": 85e6, "engine_loop": "stream",
                         "decisions_per_launch": 4100.0}},
        {"cfg4_stream": {"dps": 82e6, "engine_loop": "stream",
                         "decisions_per_launch": 4098.0}},
    ])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "dec/launch" in out


# ----------------------------------------------------------------------
# churn (open-population) series -- docs/LIFECYCLE.md
# ----------------------------------------------------------------------

def _churn_row(dps, total_ids=4096, peak=4096, p99=None):
    row = {"dps": dps, "scenario": "flash_crowd",
           "total_ids": total_ids, "peak_clients": peak,
           "live_clients": peak // 2}
    if p99 is not None:
        row["tardiness_p99_ns"] = p99
    return row


def write_history_churn(tmp_path, rows):
    h = tmp_path / "history"
    h.mkdir()
    for i, row in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"churn_flash_crowd": row}}))
    return h


def test_churn_series_judged_with_population_tag(monkeypatch, capsys,
                                                 tmp_path):
    hist = write_history_churn(tmp_path, [
        _churn_row(4e6), _churn_row(5e6), _churn_row(4.5e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "churn_flash_crowd[N=4096]" in out
    assert "peak 4096 / live 2048 clients" in out
    assert "OK" in out


def test_churn_regression_fails(monkeypatch, capsys, tmp_path):
    hist = write_history_churn(tmp_path, [
        _churn_row(4e6), _churn_row(5e6), _churn_row(1e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out


def test_churn_population_splits_the_series(monkeypatch, capsys,
                                            tmp_path):
    # a 100k-id session must NOT be median-compared against 4096-id
    # records even under the same workload key
    hist = write_history_churn(tmp_path, [
        _churn_row(40e6), _churn_row(45e6),
        _churn_row(4e6, total_ids=100_000, peak=100_000)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "not judged" in out


def test_churn_tardiness_warns_like_cfg4(monkeypatch, capsys,
                                         tmp_path):
    hist = write_history_churn(tmp_path, [
        _churn_row(4e6, p99=2e6), _churn_row(4e6, p99=2e6),
        _churn_row(4e6, p99=50e6)])
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    monkeypatch.setattr(bg, "HISTORY", hist)
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0                       # warn-only
    assert "WARNING p99 tardiness" in cap.err


def write_history_slo(tmp_path, rows):
    """rows = [(dps, violations, share_err)] -- the bench.py --slo
    scalars ride the workload row like tardiness does."""
    h = tmp_path / "history"
    h.mkdir()
    for i, (dps, viol, serr) in enumerate(rows):
        (h / f"bench_{4000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"cfg4": {
                 "dps": dps, "slo_violations_total": viol,
                 "slo_worst_share_err": serr}}}))
    return h


def test_slo_series_ok_when_stable(monkeypatch, capsys, tmp_path):
    hist = write_history_slo(tmp_path, [(40e6, 3, 0.2),
                                        (42e6, 4, 0.25),
                                        (41e6, 3, 0.22)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "slo violations" in out and "OK" in out
    assert "worst-window share err" in out


def test_slo_violation_burst_warns_but_passes(monkeypatch, capsys,
                                              tmp_path):
    # burn-rate episodes 10x the median while throughput held: the
    # QoS contract regressed -- warn-only, same policy as tardiness
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_slo(tmp_path,
                                          [(40e6, 3, 0.2),
                                           (42e6, 4, 0.2),
                                           (41e6, 40, 0.2)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING slo violations" in cap.err
    assert "burn-rate episodes up" in cap.err


def test_slo_share_err_warns_but_passes(monkeypatch, capsys,
                                        tmp_path):
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_slo(tmp_path,
                                          [(40e6, 3, 0.2),
                                           (42e6, 3, 0.25),
                                           (41e6, 3, 1.8)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING worst-window share error" in cap.err


def test_slo_clean_history_floored(monkeypatch, capsys, tmp_path):
    # a historically-clean series (median 0 violations, ~0 share err)
    # must not warn on one stray episode / 5% windowing noise
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_slo(tmp_path,
                                          [(40e6, 0, 0.0),
                                           (42e6, 0, 0.01),
                                           (41e6, 1, 0.04)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING slo" not in cap.err
    assert "WARNING worst-window" not in cap.err


def test_slo_not_judged_without_history(monkeypatch, capsys,
                                        tmp_path):
    hist = write_history_slo(tmp_path, [(40e6, 3, 0.2)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "not judged" in out


def write_history_capacity(tmp_path, rows):
    """rows = [(dps, compile_ms, retraces)] or a dict row -- the
    capacity plane's per-workload compile record (bench.py; docs/
    OBSERVABILITY.md "Capacity plane")."""
    h = tmp_path / "history"
    h.mkdir(parents=True)
    for i, row in enumerate(rows):
        if isinstance(row, tuple):
            dps, cms, rt = row
            row = {"dps": dps, "compile_ms_total": cms,
                   "retraces": rt}
        (h / f"bench_{5000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"cfg4": row}}))
    return h


def test_compile_series_ok_when_stable(monkeypatch, capsys, tmp_path):
    hist = write_history_capacity(tmp_path, [(40e6, 900.0, 0),
                                             (42e6, 1100.0, 0),
                                             (41e6, 1000.0, 0)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "compile 1000ms vs median" in out and "OK" in out
    assert "retraces 0 vs median" in out


def test_compile_blowup_warns_but_passes(monkeypatch, capsys,
                                         tmp_path):
    # a >tolerance compile-wall regression (the >15-min-Mosaic shape)
    # while dec/s held: warn-only, like the dispatch-tax series
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_capacity(tmp_path,
                                               [(40e6, 900.0, 0),
                                                (42e6, 1100.0, 0),
                                                (41e6, 9000.0, 0)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING compile" in cap.err
    assert "compile wall regressed" in cap.err


def test_retrace_churn_warns_but_passes(monkeypatch, capsys,
                                        tmp_path):
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_capacity(tmp_path,
                                               [(40e6, 900.0, 0),
                                                (42e6, 950.0, 1),
                                                (41e6, 980.0, 9)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING retraces 9" in cap.err
    assert "argument signature is churning" in cap.err


def test_compile_clean_history_floored(monkeypatch, capsys, tmp_path):
    # floors: sub-100ms compile medians and a first stray retrace are
    # cache-hit noise, not regressions -- a clean history never flaps
    monkeypatch.setattr(bg, "HISTORY",
                        write_history_capacity(tmp_path,
                                               [(40e6, 20.0, 0),
                                                (42e6, 30.0, 0),
                                                (41e6, 150.0, 1)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING compile" not in cap.err
    assert "WARNING retraces" not in cap.err


def test_capacity_skipped_rows_excluded_and_not_judged(monkeypatch,
                                                       capsys,
                                                       tmp_path):
    # a capacity-gate skip row (projected HBM over budget) neither
    # enters the medians nor gets judged as a 0-dps regression
    skip = {"dps": 0.0, "capacity_skipped": True,
            "projected_hbm_bytes": 32 << 30,
            "hbm_budget_bytes": 16 << 30}
    hist = write_history_capacity(
        tmp_path, [(40e6, 900.0, 0), (42e6, 950.0, 0), skip])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "SKIPPED by the capacity gate" in out
    # and a skip row in the PRIOR history must not drag the median
    hist2 = write_history_capacity(
        tmp_path / "h2",
        [(40e6, 900.0, 0), skip, (42e6, 950.0, 0), (41e6, 940.0, 0)])
    rc2, out2 = run_guard(monkeypatch, capsys, hist2)
    assert rc2 == 0
    assert "REGRESSION" not in out2


# ----------------------------------------------------------------------
# provenance series (margin_p99_ns / starvation_max_ns; warn-only)
# ----------------------------------------------------------------------

def write_history_prov(tmp_path, rows):
    """rows = [(dps, margin_p99_ns, starvation_max_ns, provenance_on)]
    on one device."""
    h = tmp_path / "history"
    h.mkdir()
    for i, (dps, mp99, sv, provon) in enumerate(rows):
        wl = {"dps": dps, "provenance_on": provon}
        if provon:
            wl["margin_p99_ns"] = mp99
            wl["starvation_max_ns"] = sv
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"cfg4": wl}}))
    return h


def test_prov_series_ok_when_stable(monkeypatch, capsys, tmp_path):
    hist = write_history_prov(tmp_path, [
        (40e6, 8e6, 2e8, True), (42e6, 6e6, 3e8, True),
        (41e6, 7e6, 2.5e8, True)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "margin p99" in out and "starvation max" in out
    assert "OK" in out


def test_margin_collapse_warns_but_passes(monkeypatch, capsys,
                                          tmp_path):
    # margins collapsed 10x below the median while dec/s held: the
    # proportional race tightened -- warn-only, exit 0
    monkeypatch.setattr(bg, "HISTORY", write_history_prov(
        tmp_path, [(40e6, 8e6, 1e8, True), (42e6, 10e6, 1e8, True),
                   (41e6, 0.5e6, 1e8, True)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING margin p99" in cap.err
    assert "margins collapsed" in cap.err


def test_margin_noise_floor_never_flaps(monkeypatch, capsys,
                                        tmp_path):
    # a history whose margins are already sub-ms octave noise must
    # not warn whatever the newest value does
    hist = write_history_prov(tmp_path, [
        (40e6, 0.3e6, 1e8, True), (42e6, 0.4e6, 1e8, True),
        (41e6, 0.01e6, 1e8, True)])
    monkeypatch.setattr(bg, "HISTORY", hist)
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING margin" not in cap.err


def test_starvation_growth_warns_but_passes(monkeypatch, capsys,
                                            tmp_path):
    monkeypatch.setattr(bg, "HISTORY", write_history_prov(
        tmp_path, [(40e6, 8e6, 2e8, True), (42e6, 8e6, 3e8, True),
                   (41e6, 8e6, 30e8, True)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING starvation max" in cap.err
    assert "explain.py" in cap.err


def test_starvation_floor_never_flaps(monkeypatch, capsys, tmp_path):
    # sub-100ms watermarks are one-epoch scheduling jitter: the
    # floored median (1e8) absorbs a 50x "growth" from 1ms to 150ms
    hist = write_history_prov(tmp_path, [
        (40e6, 8e6, 1e6, True), (42e6, 8e6, 2e6, True),
        (41e6, 8e6, 1.5e8, True)])
    monkeypatch.setattr(bg, "HISTORY", hist)
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING starvation" not in cap.err


def test_provenance_off_rows_split_the_series(monkeypatch, capsys,
                                              tmp_path):
    # a provenance-off session: its dps never enters the on-series
    # medians, its tag prints [prov-off], and on-rows' provenance
    # scalars never compare against it (it has none)
    hist = write_history_prov(tmp_path, [
        (40e6, 8e6, 1e8, True), (42e6, 8e6, 1e8, True),
        (10e6, 0, 0, False)])   # 4x "drop" -- but a DIFFERENT series
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "[prov-off]" in out
    assert "not judged" in out


def test_provenance_on_medians_unpolluted_by_off_rows(monkeypatch,
                                                      capsys,
                                                      tmp_path):
    # two off-rows at 10x the rate must not raise the on-series
    # median past the newest on-row's floor
    hist = write_history_prov(tmp_path, [
        (400e6, 8e6, 1e8, False), (400e6, 8e6, 1e8, False),
        (40e6, 8e6, 1e8, True), (42e6, 8e6, 1e8, True),
        (41e6, 8e6, 1e8, True)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "REGRESSION" not in out


# -- mesh serving plane series (bench.py --mode mesh) -----------------

def _mesh_row(dps, *, shards=8, sync=1, per_shard=None):
    per = per_shard if per_shard is not None else dps / shards
    return {"dps": dps, "engine_loop": "mesh", "n_shards": shards,
            "counter_sync_every": sync, "dps_per_shard_mean": per,
            "clients_total": 100_000,
            "clients_per_shard": 100_000 // shards}


def write_history_mesh(tmp_path, rows):
    h = tmp_path / "history"
    h.mkdir()
    for i, row in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"mesh": row}}))
    return h


def test_mesh_series_judged_with_shard_tag(monkeypatch, capsys,
                                           tmp_path):
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(90e6), _mesh_row(85e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "mesh[S=8,K=1,N=100000,P=static]" in out
    assert "/shard aggregate-of-8" in out
    assert "OK" in out


def test_mesh_regression_fails(monkeypatch, capsys, tmp_path):
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(90e6), _mesh_row(20e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out


def test_mesh_shard_count_splits_the_series(monkeypatch, capsys,
                                            tmp_path):
    # an 8-shard aggregate must NOT be median-compared against
    # 1-shard records even under the same workload key
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6, shards=8), _mesh_row(90e6, shards=8),
        _mesh_row(11e6, shards=1)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "mesh[S=1,K=1,N=100000,P=static]" in out
    assert "not judged" in out


def test_mesh_sync_cadence_splits_the_series(monkeypatch, capsys,
                                             tmp_path):
    # K=4 sessions exchange 4x fewer counters -- a different machine,
    # never compared against K=1 records in either direction
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6, sync=1), _mesh_row(90e6, sync=1),
        _mesh_row(20e6, sync=4)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "mesh[S=8,K=4,N=100000,P=static]" in out
    assert "not judged" in out


def test_mesh_per_shard_collapse_warns_but_passes(monkeypatch,
                                                  capsys, tmp_path):
    # aggregate holds (more shards papering over a slower engine) but
    # per-shard dec/s collapsed: warn-only, never a hard failure
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6, per_shard=10e6),
        _mesh_row(88e6, per_shard=11e6),
        _mesh_row(80e6, per_shard=2e6)])
    monkeypatch.setattr(bg, "HISTORY", hist)
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING per-shard" in cap.err
    assert "REGRESSION" not in cap.out


def test_mesh_per_shard_stable_ok(monkeypatch, capsys, tmp_path):
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(88e6), _mesh_row(84e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "per-shard 10.50M vs median" in out


def test_mesh_client_population_splits_the_series(monkeypatch,
                                                  capsys, tmp_path):
    # a 1M-client session legitimately runs slower per aggregate
    # (per-epoch work grows with N, decisions stay bounded by m*k) --
    # it must NOT be median-compared against 100k-client records
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(90e6),
        dict(_mesh_row(8e6), clients_total=1_000_000,
             clients_per_shard=125_000)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "mesh[S=8,K=1,N=1000000,P=static]" in out
    assert "not judged" in out


# -- chaos (fault-bearing) mesh rows (bench.py --fault-plan <spec>) ---

def _chaos_mesh_row(dps, **over):
    row = _mesh_row(dps)
    row.update({"fault_plan": "T32xS8:drop12+resync11+inject138",
                "fault_dropouts_per_shard": [2] * 8,
                "fault_resyncs_per_shard": [1] * 8}, **over)
    return row


def test_chaos_mesh_row_not_judged(monkeypatch, capsys, tmp_path):
    # the newest row bears a fault plan: its rate reflects injected
    # dropouts, not the engine -- announced, never judged, rc 0 even
    # though the rate cratered
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(90e6), _chaos_mesh_row(5e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "chaos (fault-injection) row" in out
    assert "REGRESSION" not in out


def test_chaos_mesh_rows_excluded_from_medians(monkeypatch, capsys,
                                               tmp_path):
    # two prior chaos rows at 1/10th the clean rate must not drag the
    # clean median under the newest clean row's floor
    hist = write_history_mesh(tmp_path, [
        _chaos_mesh_row(8e6), _chaos_mesh_row(9e6),
        _mesh_row(80e6), _mesh_row(90e6), _mesh_row(84e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "REGRESSION" not in out
    assert "vs median 85.0M over 2 sessions" in out


def test_chaos_mesh_medians_unpolluted_upward(monkeypatch, capsys,
                                              tmp_path):
    # the mirror direction: a chaos row at 10x must not RAISE the
    # clean median and fail an honest clean session
    hist = write_history_mesh(tmp_path, [
        _chaos_mesh_row(900e6), _chaos_mesh_row(950e6),
        _mesh_row(80e6), _mesh_row(90e6), _mesh_row(84e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "REGRESSION" not in out


def test_chaos_row_prints_dropout_accounting(monkeypatch, capsys,
                                             tmp_path):
    hist = write_history_mesh(tmp_path, [
        _mesh_row(80e6), _mesh_row(90e6), _chaos_mesh_row(40e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "fault_plan 'T32xS8:drop12+resync11+inject138'" in out
    assert "dropouts [2, 2, 2, 2, 2, 2, 2, 2]" in out


# -- controller A/B sessions (bench.py --mode controller) -------------

def _ctl_row(dps, *, decisions=2, sides="both"):
    return {"workload": "controller", "dps": dps,
            "scenario": "shard_skew", "total_ids": 192,
            "engine_loop": "stream", "controller": sides,
            "controller_decisions": decisions,
            "recovered_dps": 1e4, "burn_epochs_on": 8,
            "burn_epochs_off": 20}


def _ctl_rec(row, **extra):
    return {"platform": "tpu", "device": "tpu0",
            "controller": row.get("controller", "both"),
            "workloads": {"controller_shard_skew": row}, **extra}


def test_controller_actuated_newest_not_judged(monkeypatch, capsys,
                                               tmp_path):
    # the newest session's controller actually actuated: its on-twin
    # wall time includes knob transitions + recompiles -- announced,
    # never judged, rc 0 even though the rate cratered
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 45e6)])
    (hist / "bench_2000.json").write_text(json.dumps(
        _ctl_rec(_ctl_row(2e6, decisions=3))))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "controller-actuated session" in out
    assert "3 journaled decision(s)" in out
    assert "REGRESSION" not in out


def test_controller_actuated_priors_excluded_from_medians(
        monkeypatch, capsys, tmp_path):
    # actuated records in the prior set must not drag the clean
    # median down and mask a real regression on a bare session
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 44e6)])
    (hist / "bench_1500.json").write_text(json.dumps(
        _ctl_rec(_ctl_row(0.2e6))))
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "tpu", "device": "tpu0",
         "workloads": {"serve": {"dps": 10e6}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out
    assert "controller-actuated record(s)" in out


def test_controller_zero_decisions_is_clean_and_tagged(monkeypatch,
                                                       capsys,
                                                       tmp_path):
    # a controller session that never actuated IS a clean run (the
    # digest gate pins it bit-identical to the bare runner): judged
    # against its own ctl-tagged series, actuation count printed
    h = tmp_path / "history"
    h.mkdir()
    for i, dps in enumerate((30e6, 34e6, 31e6)):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            _ctl_rec(_ctl_row(dps, decisions=0))))
    rc, out = run_guard(monkeypatch, capsys, h)
    assert rc == 0
    assert "controller_shard_skew[stream][N=192][ctl=both]" in out
    assert "0 controller actuation(s)" in out
    assert "OK" in out


def test_controller_tag_splits_the_series(monkeypatch, capsys,
                                          tmp_path):
    # zero-actuation controller rows at 10x the bare rate must not
    # RAISE the bare serve median and fail an honest clean session
    # (record-level exclusion does not bite at zero decisions, so
    # the row-level series identity is what protects the medians)
    hist = write_history(tmp_path, [("tpu0", 40e6), ("tpu0", 44e6)])
    for ts, dps in ((1500, 400e6), (1501, 420e6)):
        (hist / f"bench_{ts}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "controller": "both",
             "workloads": {"serve": {
                 "dps": dps, "controller": "both",
                 "controller_decisions": 0}}}))
    (hist / "bench_2000.json").write_text(json.dumps(
        {"platform": "tpu", "device": "tpu0",
         "workloads": {"serve": {"dps": 35e6}}}))
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "REGRESSION" not in out
    assert "vs median 42.0M over 2 sessions" in out


# -- rpc ingest plane sessions (bench.py --mode rpc; docs/RPC.md) -----

def _rpc_row(dps, *, workers=4, scenario="none", drops=0,
             lat99=20.0, digest_match=True):
    return {"workload": "rpc", "dps": dps, "scenario": scenario,
            "workers": workers, "requests_per_worker": 64,
            "ingest_drops": drops, "lat_p99_ms": lat99,
            "lat_p50_ms": lat99 / 4, "digest_match": digest_match,
            "chaos_exact": True}


def write_history_rpc(tmp_path, rows):
    h = tmp_path / "history"
    h.mkdir()
    for i, row in enumerate(rows):
        (h / f"bench_{1000 + i}.json").write_text(json.dumps(
            {"platform": "tpu", "device": "tpu0",
             "workloads": {"rpc": row}}))
    return h


def test_rpc_series_judged_with_scenario_worker_tag(monkeypatch,
                                                    capsys, tmp_path):
    hist = write_history_rpc(tmp_path, [
        _rpc_row(4e6), _rpc_row(5e6), _rpc_row(4.5e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "rpc[none,W=4]" in out
    assert "OK" in out


def test_rpc_regression_fails(monkeypatch, capsys, tmp_path):
    hist = write_history_rpc(tmp_path, [
        _rpc_row(4e6), _rpc_row(5e6), _rpc_row(1e6)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 1 and "REGRESSION" in out


def test_rpc_worker_count_splits_the_series(monkeypatch, capsys,
                                            tmp_path):
    # an 8-worker session drives different arrival concurrency than a
    # 4-worker one -- never median-compared even under the same key
    hist = write_history_rpc(tmp_path, [
        _rpc_row(40e6), _rpc_row(45e6), _rpc_row(4e6, workers=8)])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "rpc[none,W=8]" in out and "not judged" in out


def test_rpc_rows_never_pollute_non_rpc_medians(monkeypatch, capsys,
                                                tmp_path):
    # the workers key joins the series identity from BOTH sides: two
    # rpc-shaped rows under a colliding workload key must not drag a
    # bare workload's median
    hist = write_history_rows(tmp_path, [
        {"serve": {"dps": 40e6}},
        {"serve": {"dps": 44e6}},
        {"serve": _rpc_row(1e6)},
        {"serve": _rpc_row(1.2e6)},
        {"serve": {"dps": 38e6}},
    ])
    rc, out = run_guard(monkeypatch, capsys, hist)
    assert rc == 0
    assert "REGRESSION" not in out
    assert "vs median 42.0M over 2 sessions" in out


def test_rpc_ingest_drop_growth_warns_but_passes(monkeypatch, capsys,
                                                 tmp_path):
    # device clamp discards 5x past the floored median while dec/s
    # held: warn-only -- drop counts ride arrival timing over real
    # sockets, a hard gate would flap
    monkeypatch.setattr(bg, "HISTORY", write_history_rpc(
        tmp_path, [_rpc_row(4e6, drops=0), _rpc_row(4.2e6, drops=0),
                   _rpc_row(4.1e6, drops=5)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING ingest drops" in cap.err
    assert "overrunning wave capacity" in cap.err


def test_rpc_lat_p99_growth_warns_but_passes(monkeypatch, capsys,
                                             tmp_path):
    monkeypatch.setattr(bg, "HISTORY", write_history_rpc(
        tmp_path, [_rpc_row(4e6, lat99=60.0),
                   _rpc_row(4.2e6, lat99=70.0),
                   _rpc_row(4.1e6, lat99=400.0)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING admit->commit p99" in cap.err
    assert "end-to-end tail regressed" in cap.err


def test_rpc_clean_history_floors_never_flap(monkeypatch, capsys,
                                             tmp_path):
    # a clean-drop history (median 0, floored at 1) must not warn on
    # one stray clamp, and sub-50ms p99 medians must not warn on
    # wall-clock jitter under the 50ms floor
    monkeypatch.setattr(bg, "HISTORY", write_history_rpc(
        tmp_path, [_rpc_row(4e6, drops=0, lat99=10.0),
                   _rpc_row(4.2e6, drops=0, lat99=15.0),
                   _rpc_row(4.1e6, drops=1, lat99=90.0)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING ingest drops" not in cap.err
    assert "WARNING admit->commit" not in cap.err


def test_rpc_digest_mismatch_warns(monkeypatch, capsys, tmp_path):
    # the bench's own digest gate (live vs journaled-trace replay)
    # failed: surfaced loudly on stderr even though throughput held
    monkeypatch.setattr(bg, "HISTORY", write_history_rpc(
        tmp_path, [_rpc_row(4e6), _rpc_row(4.2e6),
                   _rpc_row(4.1e6, digest_match=False)]))
    monkeypatch.setattr(sys, "argv", ["bench_guard.py"])
    rc = bg.main()
    cap = capsys.readouterr()
    assert rc == 0
    assert "WARNING rpc digest MISMATCH" in cap.err
    assert "not crash-equivalent" in cap.err
