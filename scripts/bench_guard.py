#!/usr/bin/env python
"""Drift-aware benchmark regression guard.

Session-to-session rates drift by the hour (RESULTS.md quotes
31-49M for one shape across sessions, ~±30%), so a naive
newest-vs-previous comparison would flap.  Instead every `bench.py`
run appends its per-workload rates to `benchmark/history/` (one JSON
per session), and this guard compares the NEWEST record of each
workload against the MEDIAN of the prior records: a drop past the
tolerance factor (default 2×, chosen to clear the observed ±30%
session noise with margin while still catching the order-of-magnitude
regressions that matter, e.g. a fastpath falling back to the serial
scan) fails CI.

Usage:
    python scripts/bench_guard.py [--tolerance 2.0] [--min-records 2]

Exit 0 when there is not enough history yet (the guard cannot judge a
first session), when every workload's newest rate clears
median/tolerance, or when run on a box with no history at all; exit 1
on a regression.

p99 reservation tardiness (the device-ledger QoS column bench.py
records since the telemetry plane landed) is tracked as its own
history series per workload and WARNED on -- tail QoS regressions
surface even when throughput held, but the log2-quantized octaves
and calibration-dependent equilibria make a hard gate flap.

dispatch_ms_per_launch (the span-tracer dispatch-tax column bench.py
records under --spans) gets the same treatment: its own per-workload
series, warn-only on >tolerance regressions -- the dispatch tax can
regress structurally (a lost fusion, an extra sync) while dec/s holds
because the chains amortize it, and it is the before/after currency
of the streaming-serve-loop work (ROADMAP #1).

Churn workloads (bench.py --mode churn; docs/LIFECYCLE.md) form
their own per-workload series keyed additionally by scenario +
scripted population size (total_ids): the population is DYNAMIC, so
the record carries peak/live client counts next to the rate and a
session against a different id space never enters the medians.  The
p99-tardiness warn thresholds apply to churn series like any other.

compile_ms_total and retraces (the capacity plane's per-workload
compile record, docs/OBSERVABILITY.md "Capacity plane") are tracked
the same warn-only way: a compile-time regression or a retrace storm
can eat a whole silicon session (PROFILE.md records a >15-minute
Mosaic compile) while dec/s of the epochs that DID run holds.  Both
medians are floored (100ms / 1 retrace) so clean histories never flap
on jitter or a first stray retrace.  Workload rows the capacity gate
skipped (projected HBM over budget; "capacity_skipped": true) are
excluded from every median and never judged -- a skip is a capacity
verdict, not a rate.

margin_p99_ns and starvation_max_ns (the provenance plane's
per-workload scalars, docs/OBSERVABILITY.md "Provenance plane") are
warn-only series too: a COLLAPSING margin p99 means decisions got
contested (the proportional race tightened -- a QoS-fragility signal
even when dec/s held), and a GROWING starvation watermark means some
backlogged client sat unserved longer.  Both medians are floored (1ms
margin / 100ms starvation, one epoch of virtual time) so log2-bucket
quantization and calibration shifts never flap a clean history.
Provenance-off sessions ("provenance_on": false) form their own
series identity and are never compared against provenance-on records
in either direction.

Controller sessions (bench.py --mode controller; docs/CONTROLLER.md)
carry the `controller` tag ("on"/"both") in the series identity --
a closed-loop A/B row is never median-compared against a bare row --
and a record whose controller actually ACTUATED (>= 1 journaled
decision) joins the clean-median exclusion set the same way chaos
and restart-bearing records do: the on-twin's wall time includes
actuation recompiles, so it extends the trajectory but never seeds
nor is judged against the clean medians.  The actuation count is
printed next to the rate so a knob-thrashing session is visible at
a glance.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
HISTORY = REPO / "benchmark" / "history"


def load_records():
    """History records sorted oldest -> newest (filename carries the
    timestamp; bench.py writes bench_<unix_ts>.json)."""
    if not HISTORY.is_dir():
        return []
    recs = []
    for p in sorted(HISTORY.glob("bench_*.json")):
        try:
            recs.append((p.name, json.loads(p.read_text())))
        except (OSError, json.JSONDecodeError) as e:
            print(f"bench_guard: unreadable {p.name}: {e}",
                  file=sys.stderr)
    return recs


def median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def is_fallback(rec: dict) -> bool:
    """A backend-fallback session: an older bench.py that could not
    initialize the accelerator ran (a reduced shape) on cpu (today's
    bench refuses to run off the chip).  Their rates are not
    comparable to accelerator sessions -- the guard annotates them and
    keeps them out of the medians."""
    return bool(rec.get("fallback")) or rec.get("platform") == "cpu"


def wheel_kernel(row: dict) -> str:
    """The wheel bucket kernel a row ran (older rows carry it as
    ``wheel_kernel_effective``; non-wheel rows == xla)."""
    return row.get("wheel_kernel_effective",
                   row.get("wheel_kernel", "xla"))


def is_chaos(rec: dict) -> bool:
    """A fault-injection session (bench.py --fault-plan != "none"):
    its rates reflect injected dropouts/skew, not the engine, so it
    never enters the clean-run medians and is never judged against
    them (docs/ROBUSTNESS.md).  Records predating the field are
    clean runs."""
    return rec.get("fault_plan", "none") != "none"


def is_restarted(rec: dict) -> bool:
    """A supervised session that actually restarted (bench.py under
    robust.supervisor with DMCLOCK_RESTARTS > 0): its wall time
    includes resume + replay recovery work, so like a chaos session
    it extends the trajectory but never enters -- and is never judged
    against -- the clean-run medians.  A supervised run with ZERO
    restarts is a clean run (the zero-host-fault gate pins it
    bit-identical to the bare runner)."""
    return bool(rec.get("supervised")) and int(rec.get("restarts",
                                                       0) or 0) > 0


def is_controller_actuated(rec: dict) -> bool:
    """A closed-loop controller session that actually ACTUATED
    (bench.py --mode controller with >= 1 journaled decision): the
    on-twin's wall time includes knob transitions and their
    recompiles, so like a chaos session it extends the trajectory
    but never enters -- and is never judged against -- the clean-run
    medians.  A controller session with ZERO decisions is a clean
    run (the PR-18 digest gate pins controller=off -- and an
    actuation-free controller=on -- bit-identical to the bare
    runner).  Records predating the field are bare runs."""
    if rec.get("controller", "off") == "off":
        return False
    return any(int(row.get("controller_decisions", 0) or 0) > 0
               for row in rec.get("workloads", {}).values())


def is_degraded(rec: dict) -> bool:
    """A session where the degradation ladder stepped a fast path
    down mid-run (bench.py records the step list): the rates are
    honest for the EFFECTIVE impl, but the step itself means
    something failed -- the record must neither seed clean-run
    medians nor pass silently as a normal session, or a real
    fast-path regression could masquerade as a benign step-down."""
    return bool(rec.get("degradation_ladder"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=2.0,
                    help="fail when newest < median(prior)/tolerance")
    ap.add_argument("--min-records", type=int, default=2,
                    help="prior records needed before judging")
    args = ap.parse_args()

    recs = load_records()
    if not recs:
        print("bench_guard: no history yet -- pass (bench.py appends "
              "benchmark/history/ records on real hardware)")
        return 0

    n_fb = sum(1 for _, r in recs if is_fallback(r))
    if n_fb:
        print(f"bench_guard: {n_fb} backend-fallback record(s) in "
              "history -- annotated, excluded from medians")
    n_chaos = sum(1 for _, r in recs if is_chaos(r))
    if n_chaos:
        print(f"bench_guard: {n_chaos} chaos (fault-injection) "
              "record(s) in history -- excluded from clean-run "
              "medians")
    n_restarted = sum(1 for _, r in recs if is_restarted(r))
    if n_restarted:
        print(f"bench_guard: {n_restarted} restart-bearing "
              "supervised record(s) in history -- excluded from "
              "clean-run medians")
    n_ctl = sum(1 for _, r in recs if is_controller_actuated(r))
    if n_ctl:
        print(f"bench_guard: {n_ctl} controller-actuated record(s) "
              "in history -- excluded from clean-run medians")
    n_degraded = sum(1 for _, r in recs if is_degraded(r))
    if n_degraded:
        print(f"bench_guard: {n_degraded} ladder-degraded record(s) "
              "in history -- excluded from clean-run medians")

    newest_name, newest = recs[-1]
    if is_degraded(newest):
        steps = newest.get("degradation_ladder")
        print(f"bench_guard: newest record {newest_name} stepped the "
              f"degradation ladder ({steps}) -- a fast path FAILED "
              "mid-session and was retried on its exact twin; "
              "investigate the step reason before trusting this "
              "session; not judged against clean-run history; pass",
              file=sys.stderr)
        return 0
    if is_restarted(newest):
        print(f"bench_guard: newest record {newest_name} is a "
              f"supervised session with "
              f"{newest.get('restarts')} restart(s) -- its rates "
              "include resume/replay recovery; recorded for the "
              "trajectory, not judged against clean-run history; "
              "pass")
        return 0
    if is_controller_actuated(newest):
        n_dec = sum(int(row.get("controller_decisions", 0) or 0)
                    for row in newest.get("workloads", {}).values())
        print(f"bench_guard: newest record {newest_name} is a "
              f"controller-actuated session ({n_dec} journaled "
              "decision(s)) -- its on-twin wall time includes "
              "actuation recompiles; recorded for the trajectory, "
              "not judged against clean-run history; pass")
        return 0
    if is_chaos(newest):
        print(f"bench_guard: newest record {newest_name} is a chaos "
              f"session (fault_plan "
              f"{newest.get('fault_plan')!r}) -- recorded for the "
              "trajectory, not judged against clean-run history; pass")
        return 0
    if is_fallback(newest):
        err = newest.get("backend_error") or newest.get("error") or ""
        print(f"bench_guard: newest record {newest_name} is a "
              f"backend-fallback (cpu) session"
              + (f" [{err}]" if err else "")
              + " -- not judged against accelerator history; pass")
        return 0
    # only same-device sessions are comparable: a session runs on
    # whatever chip generation is attached that day, and a device swap
    # would read as a phantom regression (or hide a real one)
    dev = newest.get("device")
    prior = [(n, r) for n, r in recs[:-1]
             if r.get("device") == dev and not is_fallback(r)
             and not is_chaos(r) and not is_restarted(r)
             and not is_controller_actuated(r)
             and not is_degraded(r)]
    def series(wl, key, impl, cal, loop, scen=None, pop=None,
               provon=True, shards=None, sync=None, wk="xla",
               ctl="off", rebal="off", placement="static",
               rpcw=None):
        """Prior values of one per-workload scalar column, filtered to
        the same fast-path identity (select_impl + calendar_impl +
        engine_loop + provenance_on) the throughput series uses.
        Churn workloads add scenario + scripted population
        (total_ids) to the identity: the POPULATION IS DYNAMIC, so a
        record against a different id space is a different workload,
        not a comparable session.  Mesh workloads (engine_loop=mesh)
        add n_shards + counter_sync_every: an 8-shard aggregate rate
        and a 1-shard rate are different machines, and a stale-view
        (K>1) session exchanges fewer counters per epoch -- neither
        may enter the other's medians in either direction.
        Controller rows (bench.py --mode controller) add the
        ``controller`` tag the same way: a closed-loop A/B row never
        median-compares against a bare row.  RPC rows (bench.py
        --mode rpc) add scenario + worker count: a 2-worker loopback
        session and an 8-worker one drive different arrival
        concurrency, and a chaos scenario's rates reflect injected
        faults -- neither may enter the other's medians.  Rows
        predating the provenance knob count as provenance-on (the
        default)."""
        return [r["workloads"][wl][key] for _, r in prior
                if wl in r.get("workloads", {})
                and key in r["workloads"][wl]
                and not r["workloads"][wl].get("capacity_skipped")
                and r["workloads"][wl].get("fault_plan",
                                           "none") == "none"
                and r["workloads"][wl].get("select_impl",
                                           "sort") == impl
                and r["workloads"][wl].get("calendar_impl",
                                           "minstop") == cal
                and r["workloads"][wl].get("engine_loop",
                                           "round") == loop
                and r["workloads"][wl].get("scenario") == scen
                and (r["workloads"][wl].get("total_ids")
                     or r["workloads"][wl].get("clients_total")) == pop
                and r["workloads"][wl].get("n_shards") == shards
                and r["workloads"][wl].get("counter_sync_every")
                == sync
                and wheel_kernel(r["workloads"][wl]) == wk
                and r["workloads"][wl].get("controller",
                                           "off") == ctl
                # the rebalance plane splits mesh series exactly like
                # the controller tag: a migrating session's rates
                # include the host-side handoffs, and a p2c-placed
                # population is a different machine than cid % S --
                # rows predating the knob == off/static
                and r["workloads"][wl].get("rebalance",
                                           "off") == rebal
                and r["workloads"][wl].get("placement",
                                           "static") == placement
                # rpc rows carry their loadgen worker count; only
                # they have the key, so non-rpc rows pass with None
                and r["workloads"][wl].get("workers") == rpcw
                and bool(r["workloads"][wl].get("provenance_on",
                                                True)) == provon]

    status = 0
    for wl, row in sorted(newest.get("workloads", {}).items()):
        dps = row.get("dps")
        if dps is None:
            continue
        if row.get("capacity_skipped"):
            # the capacity gate downgraded this workload before launch
            # (projected HBM over the device budget): a deliberate
            # skip, not a rate -- never judged, never in the medians
            print(f"bench_guard: {wl}: SKIPPED by the capacity gate "
                  f"(projected "
                  f"{row.get('projected_hbm_bytes', 0)/2**30:.2f} GiB"
                  f" vs budget "
                  f"{row.get('hbm_budget_bytes', 0)/2**30:.2f} GiB) "
                  "-- not judged")
            continue
        # the selection backend is part of the series identity: sort
        # and radix epochs are bit-identical in DECISIONS but not in
        # cost, so their rates form separate histories (a radix session
        # judged against sort medians would flap in both directions).
        # Rows without the tag predate the knob == "sort".  The
        # calendar commit scheme splits the series the same way:
        # bucketed sessions must not pollute minstop medians (rows
        # without the tag predate the knob == "minstop").
        impl = row.get("select_impl", "sort")
        cal = row.get("calendar_impl", "minstop")
        # the engine loop splits the series exactly like the fast-path
        # knobs do: a stream session's rates (one launch per chunk of
        # rounds) must NEVER be median-compared against round records
        # -- the workload keys already differ (cfg4 vs cfg4_stream),
        # and the tag filter makes it robust even if a key collides.
        # Rows without the tag predate the knob == "round".
        loop = row.get("engine_loop", "round")
        # churn rows (open population, docs/LIFECYCLE.md) carry
        # scenario + scripted id-space size; both join the series
        # identity and the tag
        scen = row.get("scenario")
        pop = row.get("total_ids")
        provon = bool(row.get("provenance_on", True))
        # mesh rows carry shard count + counter-sync cadence + the
        # client population; all three join the series identity AND
        # the tag, so an S=8 aggregate never median-compares against
        # S=1, K=1 against K=4, or a 100k-client session against a
        # 1M-client one (the churn total_ids precedent: a different
        # population is a different workload, not a comparable
        # session -- per-epoch work grows with N while decisions per
        # epoch stay bounded by m*k).  The population rides the same
        # `pop` filter column the churn rows use.
        shards = row.get("n_shards")
        sync = row.get("counter_sync_every")
        if shards is not None and pop is None:
            pop = row.get("clients_total")
        # wheel rows carry their bucket kernel (xla vs pallas):
        # decisions are bit-identical across kernels but the rates
        # are the whole A/B, so they form separate histories.  Rows
        # predating the knob (and every non-wheel row) == xla.
        wk = wheel_kernel(row)
        # controller rows (closed-loop A/B, docs/CONTROLLER.md) carry
        # which twin(s) ran; the tag joins the series identity so an
        # A/B session never median-compares against a bare one
        ctl = row.get("controller", "off")
        # rebalance rows (bench.py --mode mesh --rebalance on) carry
        # the placement mode; both join the series identity and the
        # mesh tag (P=) -- a migrating A/B row never median-compares
        # against a static mesh session
        rebal = row.get("rebalance", "off")
        placement = row.get("placement", "static")
        # rpc rows (bench.py --mode rpc) carry the loadgen worker
        # count and a chaos-scenario tag; both join the series
        # identity -- only rpc rows have the key, so everything else
        # filters on None
        rpcw = row.get("workers")
        tag = f"{wl}[{impl}]" if impl != "sort" else wl
        if cal != "minstop":
            tag += f"[{cal}]"
        if wk != "xla":
            tag += f"[{wk}]"
        if loop != "round" and loop not in wl:
            tag += f"[{loop}]"
        if scen is not None and rpcw is None:
            tag += f"[N={pop}]"
        if rpcw is not None:
            tag += f"[{scen},W={rpcw}]"
        if shards is not None:
            tag += f"[S={shards},K={sync},N={pop},P={placement}]"
        if rebal != "off":
            tag += f"[rebal={rebal}]"
        if ctl != "off":
            tag += f"[ctl={ctl}]"
        if not provon:
            tag += "[prov-off]"
        # a fault-bearing WORKLOAD ROW (bench.py --mode mesh
        # --fault-plan <spec>): its rates reflect injected dropouts
        # and skew, not the engine -- the record-level is_chaos()
        # exclusion extended to the mesh series identity, so a chaos
        # mesh row in an otherwise clean record neither seeds nor is
        # judged against the clean medians
        if row.get("fault_plan", "none") != "none":
            print(f"bench_guard: {tag}: chaos (fault-injection) row "
                  f"(fault_plan {row.get('fault_plan')!r}, "
                  f"dropouts {row.get('fault_dropouts_per_shard')}) "
                  "-- recorded for the trajectory, not judged "
                  "against clean-run medians")
            continue
        hist = series(wl, "dps", impl, cal, loop, scen, pop, provon,
                      shards, sync, wk, ctl, rebal, placement, rpcw)
        if len(hist) < args.min_records:
            print(f"bench_guard: {tag}: {dps/1e6:.1f}M "
                  f"({len(hist)} prior record(s) -- not judged)")
            continue
        med = median(hist)
        floor = med / args.tolerance
        verdict = "OK" if dps >= floor else "REGRESSION"
        # a load-generator-capped run under-reports the engine: worth
        # seeing next to any REGRESSION verdict before panicking; for
        # calendar workloads decisions-per-pass is the per-launch
        # commit depth the bucketed ladder exists to raise
        bb = row.get("bounded_by")
        dpp = row.get("decisions_per_pass")
        # decisions-per-LAUNCH is the streaming loop's acceptance
        # currency (one stream launch covers a whole chunk of rounds)
        dpl = row.get("decisions_per_launch")
        # churn sessions print their population next to the rate: a
        # dynamic population's dec/s is meaningless without it
        peak = row.get("peak_clients")
        print(f"bench_guard: {tag}: newest {dps/1e6:.1f}M vs median "
              f"{med/1e6:.1f}M over {len(hist)} sessions "
              f"(floor {floor/1e6:.1f}M at tolerance "
              f"{args.tolerance:g}x) -- {verdict}"
              + (f" [bounded by {bb}]" if bb else "")
              + (f" [{dpp:.0f} dec/pass]" if dpp else "")
              + (f" [{dpl:.0f} dec/launch]" if dpl else "")
              + (f" [peak {peak} / live {row.get('live_clients')} "
                 "clients]" if peak is not None else "")
              + (f" [{row.get('dps_per_shard_mean', 0)/1e6:.2f}M"
                 "/shard aggregate-of-"
                 f"{shards}]" if shards is not None else "")
              + (f" [{row.get('controller_decisions', 0)} "
                 "controller actuation(s)]"
                 if ctl != "off" else ""))
        if dps < floor:
            status = 1
        # per-shard dec/s (mesh rows) as its own warn-only series:
        # the AGGREGATE can hold while per-shard throughput collapses
        # (e.g. a session quietly ran more shards of a slower
        # engine), and the scaling shape -- aggregate ~ S x per-shard
        # -- is the mesh plane's whole claim, so both are tracked.
        psm = row.get("dps_per_shard_mean")
        if psm is not None:
            p_hist = series(wl, "dps_per_shard_mean", impl, cal,
                            loop, scen, pop, provon, shards, sync,
                            wk, ctl, rebal, placement)
            if len(p_hist) < args.min_records:
                print(f"bench_guard: {tag}: per-shard "
                      f"{psm/1e6:.2f}M ({len(p_hist)} prior "
                      "record(s) -- not judged)")
            else:
                p_med = median(p_hist)
                if psm < p_med / args.tolerance:
                    print(f"bench_guard: {tag}: WARNING per-shard "
                          f"dec/s {psm/1e6:.2f}M vs median "
                          f"{p_med/1e6:.2f}M over {len(p_hist)} "
                          f"sessions (< 1/{args.tolerance:g}x) -- "
                          "per-shard throughput regressed even "
                          "though the aggregate held; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: per-shard "
                          f"{psm/1e6:.2f}M vs median "
                          f"{p_med/1e6:.2f}M -- OK")
        # final shard skew (rebalance rows: max/mean of the per-shard
        # completion totals; 1.0 = level) as its own warn-only series:
        # the migration plane's whole claim is that skew comes DOWN,
        # so a session ending more skewed than tolerance x the median
        # is worth a warning even when the aggregate rate held.
        # Warn-only: skew depends on how many migrations the
        # controller authorized before the run ended, and a hard gate
        # on a ratio of counters would flap.  Median floored at 1.0
        # (perfectly level) so a history of near-level finals never
        # warns on noise.
        sk = row.get("shard_skew_final")
        if sk is not None:
            k_hist = series(wl, "shard_skew_final", impl, cal, loop,
                            scen, pop, provon, shards, sync, wk,
                            ctl, rebal, placement)
            if len(k_hist) < args.min_records:
                print(f"bench_guard: {tag}: final shard skew "
                      f"{sk:.2f} ({len(k_hist)} prior record(s) -- "
                      "not judged)")
            else:
                k_med = max(median(k_hist), 1.0)
                if sk > k_med * args.tolerance:
                    print(f"bench_guard: {tag}: WARNING final shard "
                          f"skew {sk:.2f} vs median {k_med:.2f} over "
                          f"{len(k_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- the rebalance "
                          "plane left the mesh more skewed than its "
                          "history; investigate", file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: final shard skew "
                          f"{sk:.2f} vs median {k_med:.2f} -- OK")
        # p99 reservation tardiness rides the same per-workload
        # history as its own series: a QoS regression (tail tardiness
        # UP past tolerance x the median) is worth a warning even
        # when throughput held -- the paper's contract is per-client
        # QoS, not just decisions/sec.  Warn-only: the log2 buckets
        # quantize to octaves, and tardiness equilibria legitimately
        # shift with calibration; a hard gate would flap.
        p99 = row.get("tardiness_p99_ns")
        if p99 is not None:
            t_hist = series(wl, "tardiness_p99_ns", impl, cal, loop,
                            scen, pop, provon, shards, sync, wk, ctl)
            if len(t_hist) < args.min_records:
                print(f"bench_guard: {tag}: p99 tardiness "
                      f"{p99/1e6:.2f}ms ({len(t_hist)} prior "
                      "record(s) -- not judged)")
            else:
                t_med = median(t_hist)
                # floor the median at 1ms: a perfectly-conformant
                # history (median ~0) must not warn on nanosecond
                # tails -- sub-ms p99 tardiness is octave-quantized
                # noise, not a QoS regression
                ceil = max(t_med, 1e6) * args.tolerance
                if p99 > ceil:
                    print(f"bench_guard: {tag}: WARNING p99 "
                          f"tardiness {p99/1e6:.2f}ms vs median "
                          f"{t_med/1e6:.2f}ms over {len(t_hist)} "
                          f"sessions (> {args.tolerance:g}x) -- "
                          "tail QoS regressed; investigate even "
                          "though throughput held", file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: p99 tardiness "
                          f"{p99/1e6:.2f}ms vs median "
                          f"{t_med/1e6:.2f}ms -- OK")
        # dispatch tax per launch (bench.py --spans) as its own
        # series: the chains amortize dispatch, so dec/s can hold
        # while the per-launch tax regresses structurally -- and the
        # streaming-loop PR's win must show up HERE.  Warn-only: the
        # dispatch cost drifts between sessions like the
        # rates do, and a hard gate would flap.
        disp = row.get("dispatch_ms_per_launch")
        if disp is not None:
            d_hist = series(wl, "dispatch_ms_per_launch", impl, cal,
                            loop, scen, pop, provon, shards, sync, wk, ctl)
            if len(d_hist) < args.min_records:
                print(f"bench_guard: {tag}: dispatch "
                      f"{disp:.2f}ms/launch ({len(d_hist)} prior "
                      "record(s) -- not judged)")
            else:
                d_med = median(d_hist)
                # floor the median at 1ms: sub-ms dispatch medians
                # (cpu boxes) would make µs jitter read as a 2x
                # regression
                ceil = max(d_med, 1.0) * args.tolerance
                if disp > ceil:
                    print(f"bench_guard: {tag}: WARNING dispatch "
                          f"{disp:.2f}ms/launch vs median "
                          f"{d_med:.2f}ms over {len(d_hist)} "
                          f"sessions (> {args.tolerance:g}x) -- the "
                          "per-launch dispatch tax regressed; "
                          "throughput may still hold because the "
                          "chains amortize it; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: dispatch "
                          f"{disp:.2f}ms/launch vs median "
                          f"{d_med:.2f}ms -- OK")
        # SLO plane verdicts (bench.py --slo; docs/OBSERVABILITY.md
        # "SLO plane") as their own per-workload warn-only series:
        # burn-rate episodes and the worst-window share error measure
        # delivered-vs-contract QoS, which can regress while dec/s
        # holds -- and, like tardiness, their equilibria shift with
        # calibration, so a hard gate would flap.
        viol = row.get("slo_violations_total")
        if viol is not None:
            v_hist = series(wl, "slo_violations_total", impl, cal,
                            loop, scen, pop, provon, shards, sync, wk, ctl)
            if len(v_hist) < args.min_records:
                print(f"bench_guard: {tag}: slo violations {viol} "
                      f"({len(v_hist)} prior record(s) -- not "
                      "judged)")
            else:
                v_med = median(v_hist)
                # floor the median at 1: a historically-clean series
                # must not warn on the first stray episode
                ceil = max(v_med, 1.0) * args.tolerance
                if viol > ceil:
                    print(f"bench_guard: {tag}: WARNING slo "
                          f"violations {viol} vs median {v_med:g} "
                          f"over {len(v_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- burn-rate "
                          "episodes up; the QoS contract regressed "
                          "even if throughput held; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: slo violations "
                          f"{viol} vs median {v_med:g} -- OK")
        serr = row.get("slo_worst_share_err")
        if serr is not None:
            s_hist = series(wl, "slo_worst_share_err", impl, cal,
                            loop, scen, pop, provon, shards, sync, wk, ctl)
            if len(s_hist) < args.min_records:
                print(f"bench_guard: {tag}: worst-window share err "
                      f"{serr:.3f} ({len(s_hist)} prior record(s) "
                      "-- not judged)")
            else:
                s_med = median(s_hist)
                # floor at 0.05: a 5% relative share error is inside
                # windowing noise on any population
                ceil = max(s_med, 0.05) * args.tolerance
                if serr > ceil:
                    print(f"bench_guard: {tag}: WARNING worst-window "
                          f"share error {serr:.3f} vs median "
                          f"{s_med:.3f} over {len(s_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- proportional "
                          "share drifted from the weight "
                          "entitlement; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: worst-window share "
                          f"err {serr:.3f} vs median {s_med:.3f} "
                          "-- OK")
        # compile wall per workload (the capacity plane's compile
        # record) as its own warn-only series: a compile-time
        # regression (a fusion pass giving up, a program blowup)
        # lands BEFORE the timed chains, so dec/s holds while the
        # session's setup cost explodes -- the >15-min-Mosaic-compile
        # failure mode.  Warn-only: compile time drifts between
        # sessions like everything else.
        cms = row.get("compile_ms_total")
        if cms is not None:
            c_hist = series(wl, "compile_ms_total", impl, cal, loop,
                            scen, pop, provon, shards, sync, wk, ctl)
            if len(c_hist) < args.min_records:
                print(f"bench_guard: {tag}: compile {cms:.0f}ms "
                      f"({len(c_hist)} prior record(s) -- not "
                      "judged)")
            else:
                c_med = median(c_hist)
                # floor the median at 100ms: sub-100ms compiles are
                # cache-hit noise, not a regression signal
                ceil = max(c_med, 100.0) * args.tolerance
                if cms > ceil:
                    print(f"bench_guard: {tag}: WARNING compile "
                          f"{cms:.0f}ms vs median {c_med:.0f}ms "
                          f"over {len(c_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- the workload's "
                          "compile wall regressed; a retrace storm "
                          "or program blowup can eat a whole "
                          "silicon session; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: compile {cms:.0f}ms "
                          f"vs median {c_med:.0f}ms -- OK")
        # retraces as their own series, floored at 1: a clean history
        # (median 0) must not flap on one stray retrace, but a
        # retrace count past tolerance x max(median, 1) means an
        # argument signature is churning (the watchdog's
        # retrace_storm warning is the live view of the same signal)
        rt = row.get("retraces")
        if rt is not None:
            r_hist = series(wl, "retraces", impl, cal, loop, scen,
                            pop, provon, shards, sync, wk, ctl)
            if len(r_hist) < args.min_records:
                print(f"bench_guard: {tag}: retraces {rt} "
                      f"({len(r_hist)} prior record(s) -- not "
                      "judged)")
            else:
                r_med = median(r_hist)
                ceil = max(r_med, 1.0) * args.tolerance
                if rt > ceil:
                    print(f"bench_guard: {tag}: WARNING retraces "
                          f"{rt} vs median {r_med:g} over "
                          f"{len(r_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- an argument "
                          "signature is churning; every retrace "
                          "pays a full XLA compile; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: retraces {rt} vs "
                          f"median {r_med:g} -- OK")
        # provenance margin p99 (docs/OBSERVABILITY.md "Provenance
        # plane") as a warn-only series in the COLLAPSE direction: a
        # p99 winner margin falling past tolerance BELOW the median
        # means the proportional race tightened -- decisions that used
        # to win comfortably are now contested, the QoS-fragility
        # precursor to share skew.  Median floored at 1ms: histories
        # whose margins are already octave-noise never judge.
        mp99 = row.get("margin_p99_ns")
        if mp99 is not None:
            m_hist = series(wl, "margin_p99_ns", impl, cal, loop,
                            scen, pop, provon, shards, sync, wk, ctl)
            if len(m_hist) < args.min_records:
                print(f"bench_guard: {tag}: margin p99 "
                      f"{mp99/1e6:.2f}ms ({len(m_hist)} prior "
                      "record(s) -- not judged)")
            else:
                m_med = median(m_hist)
                if m_med >= 1e6 and mp99 < m_med / args.tolerance:
                    print(f"bench_guard: {tag}: WARNING margin p99 "
                          f"{mp99/1e6:.2f}ms vs median "
                          f"{m_med/1e6:.2f}ms over {len(m_hist)} "
                          f"sessions (< 1/{args.tolerance:g}x) -- "
                          "decision margins collapsed; the "
                          "proportional race tightened even though "
                          "throughput held; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: margin p99 "
                          f"{mp99/1e6:.2f}ms vs median "
                          f"{m_med/1e6:.2f}ms -- OK")
        # starvation watermark as a warn-only series in the GROWTH
        # direction (the tardiness rule's shape): median floored at
        # 100ms -- one round of virtual time -- so an always-served
        # history never flaps on scheduling jitter
        sv = row.get("starvation_max_ns")
        if sv is not None:
            s_hist2 = series(wl, "starvation_max_ns", impl, cal,
                             loop, scen, pop, provon, shards, sync, wk, ctl)
            if len(s_hist2) < args.min_records:
                print(f"bench_guard: {tag}: starvation max "
                      f"{sv/1e6:.0f}ms ({len(s_hist2)} prior "
                      "record(s) -- not judged)")
            else:
                s_med = median(s_hist2)
                ceil = max(s_med, 1e8) * args.tolerance
                if sv > ceil:
                    print(f"bench_guard: {tag}: WARNING starvation "
                          f"max {sv/1e6:.0f}ms vs median "
                          f"{s_med/1e6:.0f}ms over {len(s_hist2)} "
                          f"sessions (> {args.tolerance:g}x) -- a "
                          "backlogged client sat unserved longer; "
                          "run scripts/explain.py on the slo_log "
                          "before trusting this session",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: starvation max "
                          f"{sv/1e6:.0f}ms vs median "
                          f"{s_med/1e6:.0f}ms -- OK")
        # rpc rows (bench.py --mode rpc; docs/RPC.md): the digest
        # gate already ran inside the bench (live vs journaled-trace
        # replay) -- surface a MISMATCH loudly even though the rate
        # held, since a serving plane that admits differently than
        # its journal replays is broken regardless of throughput
        if row.get("digest_match") is False:
            print(f"bench_guard: {tag}: WARNING rpc digest MISMATCH "
                  "-- the live serve and its journaled-trace replay "
                  "disagreed; the admission plane is not "
                  "crash-equivalent; investigate before trusting "
                  "this session", file=sys.stderr)
        # ingest drops (device-side clamp discards) as a warn-only
        # series in the GROWTH direction, median floored at 1: a
        # clean history must not flap on one stray clamp, but drops
        # past tolerance x the median mean the coalesce window is
        # overrunning wave capacity -- admitted work silently
        # discarded on device.  Warn-only: drops depend on arrival
        # timing over real sockets, which drifts with box load.
        idrops = row.get("ingest_drops")
        if idrops is not None and rpcw is not None:
            i_hist = series(wl, "ingest_drops", impl, cal, loop,
                            scen, pop, provon, shards, sync, wk,
                            ctl, rebal, placement, rpcw)
            if len(i_hist) < args.min_records:
                print(f"bench_guard: {tag}: ingest drops {idrops} "
                      f"({len(i_hist)} prior record(s) -- not "
                      "judged)")
            else:
                i_med = median(i_hist)
                ceil = max(i_med, 1.0) * args.tolerance
                if idrops > ceil:
                    print(f"bench_guard: {tag}: WARNING ingest "
                          f"drops {idrops} vs median {i_med:g} over "
                          f"{len(i_hist)} sessions "
                          f"(> {args.tolerance:g}x) -- the device "
                          "admission clamp is discarding more "
                          "coalesced ops; the ingest window is "
                          "overrunning wave capacity; investigate",
                          file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: ingest drops "
                          f"{idrops} vs median {i_med:g} -- OK")
        # p99 admission-to-commit latency as a warn-only series in
        # the GROWTH direction, median floored at 50ms: the serving
        # plane's end-to-end tail (socket arrival -> device commit)
        # can regress while dec/s holds (e.g. a longer coalesce
        # stall or a slower journal fsync path sits outside the
        # timed chunk).  Warn-only: wall-clock tails on a shared box
        # drift with load, and a hard gate would flap.
        lat99 = row.get("lat_p99_ms")
        if lat99 is not None and rpcw is not None:
            l_hist = series(wl, "lat_p99_ms", impl, cal, loop, scen,
                            pop, provon, shards, sync, wk, ctl,
                            rebal, placement, rpcw)
            if len(l_hist) < args.min_records:
                print(f"bench_guard: {tag}: admit->commit p99 "
                      f"{lat99:.0f}ms ({len(l_hist)} prior "
                      "record(s) -- not judged)")
            else:
                l_med = median(l_hist)
                ceil = max(l_med, 50.0) * args.tolerance
                if lat99 > ceil:
                    print(f"bench_guard: {tag}: WARNING "
                          f"admit->commit p99 {lat99:.0f}ms vs "
                          f"median {l_med:.0f}ms over {len(l_hist)} "
                          f"sessions (> {args.tolerance:g}x) -- the "
                          "serving plane's end-to-end tail "
                          "regressed even though throughput held; "
                          "investigate", file=sys.stderr)
                else:
                    print(f"bench_guard: {tag}: admit->commit p99 "
                          f"{lat99:.0f}ms vs median {l_med:.0f}ms "
                          "-- OK")
    if status:
        print(f"bench_guard: FAILED on {newest_name} -- a >"
              f"{args.tolerance:g}x drop survived the drift margin; "
              "investigate before shipping", file=sys.stderr)
    return status


if __name__ == "__main__":
    sys.exit(main())
