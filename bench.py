#!/usr/bin/env python
"""Headline benchmark: dmClock scheduling decisions/sec, arrivals included.

Three measured workloads (BASELINE.json configs), all on the
prefix-commit epoch engine (``fastpath.scan_prefix_epoch``, bit-exact
vs the serial engine -- ``tests/test_prefix.py``):

- **serve-only**: preloaded 100k-client weight steady state (the
  round-1/2 headline protocol, kept for continuity).
- **config #3 sustained**: 10k clients, uniform ClientInfo, Poisson
  arrival waves ingested ON DEVICE between serve epochs
  (``kernels.ingest_superwave``) -- the closed loop pays for ingest,
  ring traffic, and epoch boundaries.
- **config #4 sustained**: 100k clients, Zipfian weights, uniform
  reservations sized so the constraint phase takes ~half of service
  (reservation-constrained multi-tenant); Poisson arrivals scaled to
  each client's service share; both dmClock phases active every round.

The PRIMARY value is the config #4 sustained rate (arrivals included);
the metric string carries the other two plus MEASURED decision-latency
percentiles: a decision's latency is bounded by the round it rides in,
and per-round wall times are sampled from a windowed async chain (W
rounds in flight; each device_get returns when its round completes, so
successive return times are the real per-round completion intervals
with the host round-trip hidden by the pipeline).  p50/p99 are
percentiles of >= 100 such samples.

Timing: rounds/epochs are chained asynchronously on device; one scalar
digest that data-depends on every round is fetched at the end
(one sync for the whole chain).
Decision counts are read back untimed and are exact (per-batch commit
counts).  Prints ONE json line; vs_baseline is the ratio to the 10M
north star.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from dmclock_tpu.obs import spans as obsspans
from dmclock_tpu.utils.compile_cache import enable_compile_cache

# LEGACY sorted-engine cfg4 reservation rate (round-4 calibration:
# share 0.49 at the sorted engine's ~6M dec/s equilibrium; kept for
# benchmark/run_sweeps.py's sorted-engine comparison rows).  The
# shipped cfg4 bench auto-calibrates the rate to target_resv_share on
# the calendar engine (round-5 equilibrium lands near 1200/s/client
# at ~46M dec/s -- the share is a function of rate/throughput).
CFG4_RESV_RATE = 25.0


def _timed_chain(run, state, epochs: int, tracer=None):
    """Chain ``epochs`` async epoch calls with ONE digest sync; returns
    (state, total_decisions, wall_s, guards_ok, metrics).  Guards are
    collected for EVERY epoch: a mid-chain trip zeroes that epoch's
    counts, and checking only the final epoch would report the deflated
    rate as valid.  ``metrics`` is the combined on-device obs vector
    (zeros when the runner compiled with metrics off), fetched UNTIMED
    after the wall clock stops.

    With a span ``tracer`` each async epoch call records a dispatch
    span (the per-launch dispatch tax -- the call returns once
    enqueued) and the digest sync a device_compute span (the chain's
    device-side remainder); together they cover the chain wall, the
    decomposition ``--spans`` reports."""
    from profile_util import state_digest

    from dmclock_tpu.obs import device as obsdev

    t0 = time.perf_counter()
    counts, guards, mets = [], [], []
    for _ in range(epochs):
        # the span covers the async call AND the result rebind: both
        # are per-launch host bookkeeping (on cpu the rebind also
        # absorbs wall time stolen by concurrently-running compute
        # threads, which would otherwise be attributed to nothing)
        with obsspans.span(tracer, "bench.epoch", "dispatch"):
            ep = run(state, jnp.int64(0))
            state = ep.state
            counts.append(ep.count)
            guards.append(ep.guards_ok)
            mets.append(ep.metrics)
    with obsspans.span(tracer, "bench.digest_sync", "device_compute"):
        jax.device_get(state_digest(state))
    wall = time.perf_counter() - t0
    g_ok = all(bool(jax.device_get(g).all()) for g in guards)
    total = int(sum(int(jax.device_get(c).sum()) for c in counts))
    met = obsdev_np_combine(
        np.zeros(obsdev.NUM_METRICS, dtype=np.int64),
        *[jax.device_get(m) for m in mets])
    return state, total, wall, g_ok, met


def _span_window(tracer):
    """Snapshot the tracer's per-category self-time totals at the
    start of a timed region (None tracer -> None window)."""
    return None if tracer is None else tracer.category_totals()


def _span_summary(tracer, window, wall_s: float, launches: int):
    """Close a span window over the timed chains: per-category
    self-time deltas, the per-launch dispatch/device split, and the
    host-overhead share of wall time -- the dispatch-tax decomposition
    the JSON line carries (``"spans"``) and the acceptance gate
    measures (host_prep + dispatch + device_compute + fetch + drain
    must cover >= 95% of the measured wall)."""
    if tracer is None or window is None:
        return None
    now = tracer.category_totals()
    d = {c: now.get(c, 0) - window.get(c, 0)
         for c in obsspans.CATEGORIES}
    wall_ns = max(wall_s * 1e9, 1.0)
    host_ns = d["ingest"] + d["host_prep"] + d["dispatch"] + \
        d["fetch"] + d["drain"]
    covered = host_ns + d["device_compute"] + d["checkpoint"]
    launches = max(launches, 1)
    return {
        "launches": launches,
        "dispatch_ms_per_launch": d["dispatch"] / launches / 1e6,
        "device_ms_per_launch": d["device_compute"] / launches / 1e6,
        "host_overhead_frac": host_ns / wall_ns,
        "covered_frac": covered / wall_ns,
        "wall_ms": wall_ns / 1e6,
        "categories_ms": {c: v / 1e6 for c, v in d.items() if v},
    }


def epoch_cost_analysis(compiled) -> dict:
    """Normalized per-epoch attribution from
    ``jax.stages.Compiled.cost_analysis()`` (the ROADMAP per-kernel
    cost item): the stable aggregates only -- flops and bytes accessed
    -- so PROFILE.md-style breakdowns regenerate from every bench JSON
    line instead of by hand.  Backends that cannot attribute (or old
    jax) degrade to an ``error`` note, never a crash."""
    from dmclock_tpu.obs import compile_plane as _cp

    try:
        ca = compiled.cost_analysis()
    except Exception as e:      # per-backend support varies
        return {"error": f"{type(e).__name__}: {e}"}
    # ONE normalization shared with the compile plane's per-entry
    # records, so the bench row and the record cannot disagree
    return _cp.normalize_cost_analysis(ca)


def _capacity_row(out: dict, cap_cfg: dict, cp0: dict) -> dict:
    """Fold the capacity plane's per-workload record into a result
    row (docs/OBSERVABILITY.md "Capacity plane"): the compile wall +
    retraces this workload added (compile-plane totals delta), the
    projected resident HBM for its knob setting, and the roofline
    verdict joining cost_analysis flops/bytes with the span tracer's
    measured dispatch/device self-time.  Telemetry must never eat the
    measurement -- every leg degrades, none raises."""
    from dmclock_tpu.obs import capacity as obscap
    from dmclock_tpu.obs import compile_plane as _cplane

    t1 = _cplane.plane().totals()
    out["compile_ms_total"] = round(
        t1["compile_ms_total"] - cp0.get("compile_ms_total", 0.0), 3)
    out["retraces"] = int(t1["retraces"] - cp0.get("retraces", 0))
    try:
        cfg = dict(cap_cfg)
        out["projected_hbm_bytes"] = obscap.projected_hbm(
            cfg.pop("n"), **cfg)
    except Exception as e:
        out["projected_hbm_error"] = f"{type(e).__name__}: {e}"
    try:
        rl = obscap.classify_bench_row(out)
        out["roofline"] = rl
        out["bound_class"] = rl["bound_class"]
    except Exception:
        out["bound_class"] = "unknown"
    return out


def _capacity_gate(cap_cfg: dict, *, select_impl: str = "sort",
                   calendar_impl: str = "minstop",
                   engine_loop: str = "round"):
    """Pre-launch projected-HBM check (``--capacity``): when the
    projection exceeds the detected device budget the workload is
    DOWNGRADED -- a stderr warning and a tagged skip row, never an
    out-of-memory crash.  Returns None when the workload fits or no
    budget is known."""
    import sys

    from dmclock_tpu.obs import capacity as obscap

    try:
        cfg = dict(cap_cfg)
        n = cfg.pop("n")
        budget = obscap.device_hbm_budget()
        if budget is None:
            return None
        projected = obscap.projected_hbm(n, **cfg)
        ok = obscap.fits(n, budget, **cfg)
    except Exception as e:   # the gate must never kill the bench
        print(f"# capacity: projection failed "
              f"({type(e).__name__}: {e}); workload not gated",
              file=sys.stderr)
        return None
    if ok:
        return None

    def gib(v):
        return f"{v / 2**30:.2f} GiB" if v >= (1 << 28) \
            else f"{v / 2**20:.1f} MiB"

    usable = int(budget * 0.9)   # fits()'s default slack_frac
    print(f"# capacity: projected {gib(projected)} exceeds the "
          f"usable budget {gib(usable)} (device {gib(budget)} minus "
          f"10% slack) -- workload SKIPPED, not crashed (n={n}; "
          f"plan_capacity() for the fitting shape)", file=sys.stderr)
    # the skip row keeps the standard scalar keys so the metric
    # string / history plumbing never KeyErrors; bench_guard excludes
    # capacity_skipped rows from the medians and never judges them
    return {"dps": 0.0, "decisions": 0, "fill": 0.0,
            "resv_phase_frac": 0.0, "mean_depth": 0.0,
            "decisions_per_launch": 0.0,
            "select_impl": select_impl,
            "calendar_impl": calendar_impl,
            "engine_loop": engine_loop,
            "capacity_skipped": True,
            "projected_hbm_bytes": int(projected),
            "hbm_budget_bytes": int(budget),
            "cost_analysis": {}}


def _feed_cost_registry(workload: str, cost: dict) -> None:
    """Mirror the attribution into the process-wide obs registry so
    embedders that scrape it (docs/OBSERVABILITY.md) see per-epoch
    cost without parsing the bench JSON line."""
    from dmclock_tpu.obs import default_registry

    reg = default_registry()
    for key, v in cost.items():
        if isinstance(v, (int, float)):
            reg.gauge(f"dmclock_epoch_cost_{key}",
                      "XLA cost_analysis attribution of the jitted "
                      "epoch", labels={"workload": workload}).set(v)


def bench_serve_only(k: int = 65536, m: int = 32, *,
                     epochs_lo: int = 3, epochs_hi: int = 6,
                     depth: int = 320, reps: int = 5,
                     n: int = 100_000, with_metrics: bool = True,
                     select_impl: str = "sort", tag_width: int = 64,
                     window_m: int | None = None, tracer=None):
    """Preloaded weight steady state, serving only (no ingest).

    DIFFERENCED chains: a short and a long chain each pay one dispatch
    ramp + one sync, so ``(D_hi - D_lo) / (T_hi - T_lo)`` cancels the
    fixed per-chain overhead exactly -- with a large host round-trip
    a single-chain measurement of ~50ms of device work is mostly
    overhead, and round 3's two protocols disagreed 2-3x on identical
    shapes for exactly that reason.

    BOTH chains must be device-bound: a chain's wall time is
    ``max(device_time, sync round-trip)``, so if the SHORT chain sits
    under the ~100ms RTT floor the difference divides by a truncated
    delta and the rate explodes (observed: a 1-epoch lo chain
    reporting 202M where the true rate was ~39M).  Chain sizes below
    keep the lo chain at ~150ms+ of device work, and reps whose lo
    wall is at the RTT floor are discarded.

    Operating point: the round-4 k/m sweep's argmax (benchmark/
    RESULTS.md, median-of-3 differenced pairs per point): k=65536,
    with a plateau of ~36-40M across m in {21, 32, 64} (protocol
    noise +-15% -- single-shot pairs at these shapes spread 41-71M,
    hence the medians).  m amortizes the ~17ms per-epoch dispatch
    cost (m=8 is ~40% below the plateau); m=128 regresses (the
    unrolled window-select chain scales with m); k=98304 regresses
    (the int32 rebase window clamps the selection boundary,
    fill 0.64)."""
    from __graft_entry__ import _preloaded_state
    from dmclock_tpu.engine.fastpath import scan_prefix_epoch
    from dmclock_tpu.obs import device as obsdev

    state = _preloaded_state(n, depth, ring=depth)
    need = (epochs_lo + epochs_hi + 1) * m * k
    # margin 1.5x: weights are 1..4, so the heaviest class is served
    # ~1.6x the mean; chains sized to the MEAN backlog drain the
    # heavy clients mid-chain and deflate both fill and the rate
    # (measured: 70.9M at 168 serves/client mean vs 28.6M at 360).
    # Ring width itself also costs: depth 384 measured 38.8M at the
    # same k/m (wider Pallas-rotate chunking + ring traffic), so the
    # operating point keeps the smallest ring that feeds the chains.
    assert need * 1.5 <= n * depth, \
        f"backlog {n * depth} cannot feed {need} decisions " \
        "with heavy-class margin"
    # AOT lower+compile: the Compiled handle both runs the chains and
    # carries the cost_analysis attribution (one compilation, not
    # two); routed through the compile plane so the JSON line's
    # compile_ms_total / retraces cover the bench's own programs
    from dmclock_tpu.obs import compile_plane as _cplane

    cp0 = _cplane.plane().totals()
    run = _cplane.aot_record(
        "bench.serve",
        (n, k, m, depth, select_impl, tag_width, window_m,
         with_metrics),
        jax.jit(functools.partial(
            scan_prefix_epoch, m=m, k=k, anticipation_ns=0,
            with_metrics=with_metrics, select_impl=select_impl,
            tag_width=tag_width, window_m=window_m),
            donate_argnums=(0,)),
        state, jnp.int64(0))
    cost = epoch_cost_analysis(run)
    # a single differenced pair still carries host-clock jitter of the
    # chains' own order; the MEDIAN over fresh-state reps is stable
    # (measured spread of singles at this shape: 41-71M)
    from profile_util import scalar_latency

    lat = scalar_latency()
    rates, total_d, total_pot = [], 0, 0
    met = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
    win = _span_window(tracer)
    wall_total = 0.0
    launches = 0
    for rep in range(max(reps, 1)):
        if rep:
            state = _preloaded_state(n, depth, ring=depth)
        state, _, w0, _, _ = _timed_chain(run, state, 1,
                                          tracer)   # warm/compile
        state, d_lo, t_lo, g1, m1 = _timed_chain(run, state,
                                                 epochs_lo, tracer)
        state, d_hi, t_hi, g2, m2 = _timed_chain(run, state,
                                                 epochs_hi, tracer)
        assert g1 and g2, "rebase guards tripped -- untrustworthy"
        met = obsdev_np_combine(met, m1, m2)
        wall_total += w0 + t_lo + t_hi
        launches += 1 + epochs_lo + epochs_hi
        if t_hi <= t_lo or t_lo < 1.2 * lat:
            continue    # jitter-inverted or RTT-floor-bound lo chain
        rates.append((d_hi - d_lo) / (t_hi - t_lo))
        total_d += d_hi + d_lo
        total_pot += (epochs_hi + epochs_lo) * m * k
    assert rates, \
        "no valid pair: chains too short for the host RTT floor"
    out = {"dps": float(np.median(rates)), "decisions": total_d,
           "reps": [round(r / 1e6, 1) for r in rates],
           "fill": total_d / total_pot,
           "select_impl": select_impl, "tag_width": tag_width,
           "cost_analysis": cost}
    sp = _span_summary(tracer, win, wall_total, launches)
    if sp is not None:
        out["spans"] = sp
        out["dispatch_ms_per_launch"] = sp["dispatch_ms_per_launch"]
        out["host_overhead_frac"] = sp["host_overhead_frac"]
    if with_metrics:
        out["device_metrics"] = obsdev.metrics_dict(met)
    _capacity_row(out, dict(n=n, ring=depth, engine="prefix", m=m,
                            k=k, select_impl=select_impl,
                            tag_width=tag_width,
                            window_m=window_m), cp0)
    return out


def obsdev_np_combine(acc, *vecs):
    """Host-side metrics merge (counters add, hwm max) -- the shared
    numpy mirror of obs.device.metrics_combine."""
    from dmclock_tpu.obs import device as obsdev

    return obsdev.metrics_combine_np(acc, *vecs)


def _slo_result_block(out: dict, slo_eval) -> None:
    """Fold the burn-rate evaluator's verdict into a workload row:
    the readable 'slo' block plus the flat scalars bench_guard tracks
    as its own warn-only series -- ONE implementation for the
    sustained and churn workloads."""
    s = slo_eval.summary()
    out["slo"] = s
    out["slo_violations_total"] = s["violations_total"]
    out["slo_worst_share_err"] = s["worst_window_share_err"]
    out["slo_window_tardiness_p99_ns"] = s["window_tardiness_p99_ns"]
    out["slo_windows_closed"] = s["windows_closed"]


def _per_pass_cap(n: int, k: int, calendar_steps: int,
                  calendar_impl: str, ladder_levels: int) -> int:
    """Max decisions one batch/pass can commit -- the fill metric's
    denominator.  A bucketed calendar batch refreshes the per-client
    ``steps`` budget at every ladder level, so its cap scales with
    ``ladder_levels``; without the factor a bucketed run's fill would
    inflate past 1.0 and stop being comparable to the minstop series
    it is A/B'd against."""
    if not calendar_steps:
        return k
    levels = ladder_levels \
        if calendar_impl in ("bucketed", "wheel") else 1
    return n * calendar_steps * levels


def _zipf_weights(n: int, s: float = 1.1, lo: float = 0.5,
                  hi: float = 64.0) -> np.ndarray:
    """Zipf-by-rank weights, clipped to a sane QoS range and shuffled
    so slot order does not correlate with weight."""
    w = 1.0 / np.arange(1, n + 1) ** s
    w = np.clip(w / w[n // 2], lo, hi)
    rng = np.random.default_rng(7)
    rng.shuffle(w)
    return w


def _sustained_setup(n: int, ring: int, depth0: int,
                     resv_rates: np.ndarray, weights: np.ndarray,
                     resv_aligned: bool = False):
    """Preload ``depth0``-deep queues for a mixed-QoS population.

    ``resv_rates`` / ``weights`` are per-client; a zero disables that
    axis for the client (reference ClientInfo 0 -> 0 sentinel) and its
    preloaded head tag is pinned to MAX_TAG exactly as the tag kernel
    pins recomputed tags.

    ``resv_aligned`` drops the per-client reservation-phase stagger so
    reservation tags advance in lock-stepped cohorts (simultaneous-
    onset tenants); staggered tags spread each client's eligibility
    instant uniformly over its own period."""
    from dmclock_tpu.core.timebase import MAX_TAG, rate_to_inv_ns
    from dmclock_tpu.engine import init_state

    st = init_state(n, ring)
    c = np.arange(n)
    rinv = np.asarray([rate_to_inv_ns(r) for r in resv_rates],
                      dtype=np.int64)
    winv = np.asarray([rate_to_inv_ns(w) for w in weights],
                      dtype=np.int64)
    phase = ((c * 2654435761) & 0xFFFFF) / float(1 << 20)
    jitter = (phase * 2.0 * winv).astype(np.int64)
    rjit = np.zeros(n, dtype=np.int64) if resv_aligned else \
        (phase * 2.0 * rinv).astype(np.int64)
    head_resv = np.where(rinv == 0, np.int64(MAX_TAG), rinv + rjit)
    head_prop = np.where(winv == 0, np.int64(MAX_TAG), winv + jitter)
    arrivals = np.tile(np.arange(1, depth0), (n, 1)).astype(np.int64)
    q_arr = np.zeros((n, ring), dtype=np.int64)
    q_arr[:, :depth0 - 1] = arrivals
    return st._replace(
        active=jnp.ones(n, dtype=bool),
        idle=jnp.zeros(n, dtype=bool),
        order=jnp.arange(n, dtype=jnp.int64),
        resv_inv=jnp.asarray(rinv),
        weight_inv=jnp.asarray(winv),
        head_resv=jnp.asarray(head_resv),
        head_prop=jnp.asarray(head_prop),
        head_limit=jnp.full(n, -(1 << 62), dtype=jnp.int64),
        depth=jnp.full(n, depth0, dtype=jnp.int32),
        q_arrival=jnp.asarray(q_arr),
        q_cost=jnp.ones((n, ring), dtype=jnp.int64),
    )


def bench_sustained(n: int, k: int, m: int, rounds: int, *,
                    zipf: bool, resv_rate: float, dt_round_ns: int,
                    waves: int = 32, ring: int = 128,
                    depth0: int = 64, latency_rounds: int = 0,
                    rounds_lo: int = 0, resv_aligned: bool = False,
                    split_resv: float = 0.0, reps: int = 3,
                    chain_depth: int = 1, calendar_steps: int = 0,
                    target_resv_share: float = 0.0,
                    with_metrics: bool = True,
                    conformance_rounds: int = 2,
                    conformance_out: str = None,
                    select_impl: str = "sort",
                    calendar_impl: str = "minstop",
                    ladder_levels: int = 8,
                    wheel_kernel: str = "xla",
                    engine_loop: str = "round",
                    stream_chunk: int = 8,
                    telemetry: bool = True, slo: bool = False,
                    provenance: bool = True,
                    capacity_check: bool = True,
                    tracer=None, watchdog=None):
    """Closed loop: Poisson superwave ingest + prefix serve epoch per
    round, chained async on device; ingest IS inside the timed region.

    Arrival rates match each client's expected service share
    (reservation floor + weight share of the surplus), so the loop is
    sustained: queues hover around depth0 instead of draining.
    Admission is clamped to ring headroom on device (the AtLimit
    Reject/EAGAIN analog, reference dmclock_server.h:989-993).

    ``engine_loop`` (docs/ENGINE.md): "round" launches one fused
    ingest+serve round per dispatch (the PR-1..7 shape); "stream"
    fuses ``stream_chunk`` consecutive rounds into ONE launch (a
    ``lax.scan`` over the identical round body, so decisions are
    bit-identical) with the pre-generated Poisson draws uploaded as a
    block -- the launches-per-decision killer the streaming serve
    loop exists for.  Calibration / conformance / latency rounds stay
    on the round program either way (they are untimed and need the
    per-round slot outputs)."""
    from dmclock_tpu.engine import kernels
    from dmclock_tpu.engine.fastpath import (scan_calendar_epoch,
                                             scan_chain_epoch,
                                             scan_prefix_epoch)
    from dmclock_tpu.obs import compile_plane as _cplane
    from dmclock_tpu.obs import device as obsdev
    from dmclock_tpu.obs import histograms as obshist
    from profile_util import scalar_latency, state_digest

    # capacity plane (docs/OBSERVABILITY.md): the knob setting's
    # resident-HBM shape, for the pre-launch projected-HBM gate and
    # the JSON line's projected_hbm_bytes
    cap_engine = "calendar" if calendar_steps else \
        ("chain" if chain_depth > 1 else "prefix")
    cap_cfg = dict(
        n=n, ring=ring, engine=cap_engine, m=m,
        k=(calendar_steps if calendar_steps else k),
        chain_depth=chain_depth, select_impl=select_impl,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        telemetry=telemetry, slo=slo,
        stream_chunk=(stream_chunk if engine_loop == "stream" else 0))
    if capacity_check:
        skip = _capacity_gate(cap_cfg, select_impl=select_impl,
                              calendar_impl=calendar_impl,
                              engine_loop=engine_loop)
        if skip is not None:
            return skip
    cp0 = _cplane.plane().totals()

    # ``split_resv`` > 0 models split-population multi-tenancy: that
    # fraction of clients are reservation-ONLY floor tenants (w=0) and
    # the rest weight-only best-effort tenants (r=0).  Mixed-QoS
    # clients (both axes live) make the two dmClock phases alternate
    # PER DECISION at steady state -- every weight serve's reservation-
    # debt reduction (reference reduce_reservation_tags :1077-1111)
    # drags that client's reservation tag back to eligibility -- which
    # is semantically exact but serves the batch engine one-regime
    # slivers.  Disjoint populations keep each round's constraint debt
    # a coarse burst, which is also the more realistic storage-tenant
    # model (bought-floor tenants vs best-effort tenants).
    if split_resv > 0:
        n_resv = int(n * split_resv)
        w_tail = _zipf_weights(n - n_resv) if zipf else \
            np.asarray([1.0 + (i % 4) for i in range(n - n_resv)])
        weights = np.concatenate([np.zeros(n_resv), w_tail])
        resv_rates = np.concatenate(
            [np.full(n_resv, resv_rate), np.zeros(n - n_resv)])
    else:
        weights = _zipf_weights(n) if zipf else \
            np.asarray([1.0 + (i % 4) for i in range(n)])
        resv_rates = np.full(n, resv_rate)
    state = _sustained_setup(n, ring, depth0, resv_rates, weights,
                             resv_aligned=resv_aligned)

    # initial arrival-rate guess: reservation floor + weight share of
    # the surplus; calibration rounds below replace it with measured
    # per-client service so the loop is self-consistent (stable depth)
    # initial guess only: the calibration rounds replace it with
    # measured service.  Calendar mode has no [k] cap; seed with an
    # optimistic bound so calibration sees a saturated engine.
    serve_per_round = m * (n * calendar_steps if calendar_steps else k)
    resv_per_round = float(resv_rates.sum()) * (dt_round_ns / 1e9)
    surplus = max(serve_per_round - resv_per_round, 0.0)
    lam = resv_rates * (dt_round_ns / 1e9) + \
        surplus * (weights / weights.sum())
    lam = np.minimum(lam, waves - 1.0)

    cost = jnp.ones((n,), dtype=jnp.int64)
    dt_wave = dt_round_ns // waves

    # device telemetry accumulators (histograms + per-client ledger;
    # docs/OBSERVABILITY.md): threaded through every round AS CARRIED
    # STATE so chained rounds accumulate on device and the host
    # fetches once, untimed, at the end -- the async-drain discipline
    # the flight recorder uses.  The accumulation itself runs inside
    # the timed kernels (telemetry in the data path is the point);
    # --telemetry off A/Bs that cost, decisions bit-identical.
    # the SLO window block (obs.slo) rides the same donated carry:
    # windows roll between timed chains (one chain = one window,
    # fetched + re-zeroed untimed), and the burn-rate evaluator judges
    # each roll against the workload's reservation/weight contracts
    from dmclock_tpu.obs import slo as obsslo
    from dmclock_tpu.obs.alerts import SloEvaluator

    slo_plane = slo_eval = None
    if slo:
        slo_plane = obsslo.SloPlane(n, dt_epoch_ns=dt_round_ns,
                                    ring_depth=32)
        # initial contracts from the configured rates; calibration
        # rewrites resv_inv below, and the post-calibration
        # register_from_inv re-registers everyone from the DEVICE
        # arrays (a fresh contract epoch: the timed windows must be
        # judged against the floors the engine actually enforces,
        # not the pre-calibration guess)
        for c in range(n):
            slo_plane.register(c, float(resv_rates[c]),
                               float(weights[c]), 0.0)
        slo_eval = SloEvaluator(slo_plane, log=lambda _line: None)

    from dmclock_tpu.obs import provenance as obsprov

    def tele_zero(t0=0):
        out = (obshist.hist_zero(), obshist.ledger_zero(n)) \
            if telemetry else ()
        if provenance:
            # t0 = the measurement baseline: the post-calibration
            # reset must not read continuously-served clients as
            # starved since virtual t=0
            out = out + (obsprov.prov_init(n, now_ns=t0),)
        if slo:
            # the SLO block stays LAST: the per-chain roll reads and
            # replaces tele[-1]
            out = out + (slo_plane.stamp(obsslo.window_zero(n)),)
        return out

    def tele_unpack(tele):
        i = 0
        th = tl = tp = ts = None
        if telemetry:
            th, tl = tele[0], tele[1]
            i = 2
        if provenance:
            tp = tele[i]
            i += 1
        if slo:
            ts = tele[i]
        return th, tl, tp, ts

    tele = tele_zero()

    def round_fn(st, counts, t_base, tele):
        th, tl, tp, ts = tele_unpack(tele)
        headroom = jnp.maximum(
            st.ring_capacity - st.depth, 0).astype(jnp.int32)
        # admission clamp (the AtLimit Reject/EAGAIN analog); the drop
        # count feeds the on-device obs vector instead of vanishing
        counts, dropped = obsdev.admission_clamp(counts, headroom)
        wave_times = t_base + jnp.arange(waves, dtype=jnp.int64) \
            * dt_wave
        st = kernels.ingest_superwave(
            st, counts, wave_times, cost, cost, cost,
            anticipation_ns=0)
        now = t_base + dt_round_ns
        drop_met = obsdev.metrics_delta(ingest_drops=dropped) \
            if with_metrics else obsdev.metrics_zero()

        def tele_pack(ep):
            out = (ep.hists, ep.ledger) if telemetry else ()
            if provenance:
                out = out + (ep.prov,)
            return out + (ep.slo,) if slo else out
        # returns (state, count[m], guards[m], resv_decisions[m],
        # slot[m,k], length[m,k], metrics): the phase split reduces ON
        # DEVICE so per-round readbacks stay O(m) scalars; slot/length
        # are fetched only by the untimed calibration rounds (unfetched
        # device arrays cost nothing).
        if calendar_steps:
            # sortless calendar batches: per-client counts come back
            # directly ([N] served vector doubles as the calibration
            # feed; lens column unused).  calendar_impl="bucketed"
            # fuses ladder_levels refreshed-boundary commits per batch
            # (one launch = what took L minstop batches).
            ep = scan_calendar_epoch(st, now, m, steps=calendar_steps,
                                     anticipation_ns=0,
                                     with_metrics=with_metrics,
                                     calendar_impl=calendar_impl,
                                     ladder_levels=ladder_levels,
                                     wheel_kernel=wheel_kernel,
                                     hists=th, ledger=tl, slo=ts,
                                    prov=tp)
            return (ep.state, ep.count, ep.progress_ok,
                    ep.resv_count, ep.served,
                    jnp.ones_like(ep.served),
                    obsdev.metrics_combine(ep.metrics, drop_met),
                    tele_pack(ep))
        if chain_depth > 1:
            ep = scan_chain_epoch(st, now, m, k,
                                  chain_depth=chain_depth,
                                  anticipation_ns=0,
                                  with_metrics=with_metrics,
                                  select_impl=select_impl,
                                  hists=th, ledger=tl, slo=ts,
                                    prov=tp)
            units = ep.slot >= 0
            lens = ep.length.astype(jnp.int32)
            # a unit's entry serve is weight-phase iff class >= 1;
            # its induced serves are all constraint-phase
            resv = jnp.sum(jnp.where(units,
                                     lens - (ep.cls >= 1), 0),
                           axis=1).astype(jnp.int32)
        else:
            ep = scan_prefix_epoch(st, now, m, k, anticipation_ns=0,
                                   with_metrics=with_metrics,
                                   select_impl=select_impl,
                                   hists=th, ledger=tl, slo=ts,
                                    prov=tp)
            srv_pos = ep.slot >= 0
            resv = jnp.sum(srv_pos & (ep.phase == 0),
                           axis=1).astype(jnp.int32)
            lens = srv_pos.astype(jnp.int32)
        return (ep.state, ep.count, ep.guards_ok, resv, ep.slot, lens,
                obsdev.metrics_combine(ep.metrics, drop_met),
                tele_pack(ep))

    # AOT lower+compile with a zero-arrivals sample (same avals as the
    # real draws, and the Poisson stream stays byte-identical to prior
    # sessions): one compilation serves the whole bench and carries the
    # per-epoch cost_analysis attribution
    # the telemetry accumulators are donated alongside the state: they
    # are pure carried state, and an un-donated [N, 5] ledger would
    # pay a fresh HBM allocation every round
    run = _cplane.aot_record(
        "bench.round",
        (n, k, m, ring, cap_engine, select_impl, calendar_impl,
         calendar_steps, ladder_levels, wheel_kernel, chain_depth,
         telemetry, slo, with_metrics),
        jax.jit(round_fn, donate_argnums=(0, 3)),
        state, jnp.zeros((n,), jnp.int32), jnp.int64(0), tele)
    # NOT named `cost`: round_fn closes over the per-client cost
    # vector of that name, and the stream chunk re-traces round_fn
    # lazily -- shadowing it with this dict would poison the trace
    cost_attr = epoch_cost_analysis(run)
    rng = np.random.default_rng(11)

    assert engine_loop in ("round", "stream"), engine_loop
    stream_on = engine_loop == "stream"
    stream_chunk = max(int(stream_chunk), 1)
    _chunk_jits: dict = {}

    def chunk_run(c: int):
        """One device launch covering ``c`` rounds: a ``lax.scan``
        over the IDENTICAL round body (same integer ops in the same
        order -- decisions bit-identical to the round loop, gated in
        ci.sh), state + telemetry donated as carried HBM state,
        per-round count/guards/resv/metrics stacking in HBM as scan
        outputs and drained once per chunk.  AOT lower+compile (the
        round program's discipline): a lazy first-call compile would
        land inside the first timed chain and read as launch cost."""
        if c not in _chunk_jits:
            from jax import lax

            def chunk_fn(st, counts_c, t0, tele):
                def body(carry, xs):
                    st, tele = carry
                    counts, i = xs
                    out = round_fn(st, counts, t0 + i * dt_round_ns,
                                   tele)
                    return (out[0], out[7]), (out[1], out[2], out[3],
                                              out[6])

                (st, tele), outs = lax.scan(
                    body, (st, tele),
                    (counts_c, jnp.arange(c, dtype=jnp.int64)))
                return st, outs, tele

            _chunk_jits[c] = _cplane.aot_record(
                "bench.chunk",
                (n, k, m, ring, cap_engine, select_impl,
                 calendar_impl, calendar_steps, wheel_kernel,
                 telemetry, slo, with_metrics, c),
                jax.jit(chunk_fn, donate_argnums=(0, 3)),
                state, jnp.zeros((c, n), jnp.int32), jnp.int64(0),
                tele)
        return _chunk_jits[c]

    def draw():
        return jnp.asarray(
            np.minimum(rng.poisson(lam), waves).astype(np.int32))

    # warm/compile, then calibration (untimed): iterate toward the
    # self-consistent sustained equilibrium.  Each iteration measures
    # per-client service over two rounds and sets arrival rates to the
    # measured shares (arrivals == service, so the loop neither drains
    # nor hits the admission clamp).  Two adaptive corrections on top:
    #
    #  - load probing: if the queues drained (engine idle part of the
    #    round), the measured service is ARRIVAL-limited, not the
    #    engine's capacity -- scale lambda up and re-measure until the
    #    backlog holds, so the reported rate is engine-limited;
    #  - constraint-share targeting (``target_resv_share`` > 0): the
    #    share of constraint-phase decisions is an emergent property
    #    of resv_rate vs throughput, so a faster engine needs a
    #    proportionally larger reservation floor to stay at the same
    #    phase mix.  The damped multiplicative update converges in a
    #    few iterations; the measured share is reported.
    with obsspans.span(tracer, "bench.round", "dispatch"):
        state, _, _, _, _, _, _, tele = run(state, draw(),
                                            jnp.int64(0), tele)
    with obsspans.span(tracer, "bench.digest_sync", "device_compute"):
        jax.device_get(state_digest(state))
    t_base = dt_round_ns
    cal_iters = 5 if (calendar_steps or target_resv_share) else 1
    from dmclock_tpu.core.timebase import rate_to_inv_ns
    for _it in range(cal_iters):
        served = np.zeros(n, dtype=np.int64)
        resv_total = 0
        cal_rounds = 2
        for _ in range(cal_rounds):
            with obsspans.span(tracer, "bench.round", "dispatch"):
                state, cnt_, _, resv_, slot, lens, _, tele = run(
                    state, draw(), jnp.int64(t_base), tele)
            t_base += dt_round_ns
            resv_total += int(jax.device_get(resv_).sum())
            if calendar_steps:
                served += jax.device_get(slot).astype(np.int64)
            else:
                slots = jax.device_get(slot).ravel()
                cnt = jax.device_get(lens).ravel()
                ok = slots >= 0
                np.add.at(served, slots[ok], cnt[ok])
        total = int(served.sum())
        lam = np.minimum(served / cal_rounds, waves - 1.0)
        depth_mean = float(np.asarray(state.depth).mean())
        if depth_mean < 0.75 * depth0 and _it < cal_iters - 1:
            # arrival-limited: probe a higher load (clamped by waves)
            lam = np.minimum(np.maximum(lam * 1.4, lam + 0.5),
                             waves - 1.0)
        elif depth_mean > 1.5 * depth0 and _it < cal_iters - 1:
            # overloaded: back off before arrears outgrow the serve
            # budget (the calendar step cap) and the backlog spirals.
            # Guarded like the probe branch: legacy single-iteration
            # configs must keep their recorded arrivals==service
            # calibration untouched (bench_guard compares history)
            lam = lam * 0.85
        if target_resv_share and total:
            share = resv_total / max(total, 1)
            adj = float(np.clip((target_resv_share
                                 / max(share, 1e-3)) ** 0.6,
                                0.33, 3.0))
            resv_rates = resv_rates * adj
            # vectorized rate -> inverse (rate_to_inv_ns per element
            # costs seconds at n=100k x 5 iterations); same rounding
            # and sentinels as timebase.rate_to_inv_ns
            from dmclock_tpu.core.timebase import MAX_INV_NS, NS_PER_SEC
            with np.errstate(divide="ignore"):
                rinv = np.where(
                    resv_rates <= 0, 0,
                    np.minimum(np.rint(NS_PER_SEC
                                       / np.maximum(resv_rates, 1e-12)),
                               MAX_INV_NS)).astype(np.int64)
            state = state._replace(resv_inv=jnp.asarray(rinv))

    # pregenerate + upload every round's Poisson draws BEFORE timing:
    # the host RNG and the upload are the load GENERATOR, not
    # the scheduler (the reference's ns/call numbers likewise exclude
    # its client threads' own work); the on-device ingest of those
    # arrivals stays inside the timed region.
    #
    # DIFFERENCED chains (see bench_serve_only): a short chain of
    # ``rounds_lo`` and a long one of ``rounds`` each pay one dispatch
    # ramp + one sync; the difference cancels fixed overhead.  One
    # pair still carries host-clock jitter of the chains' own order
    # (single-pair cfg3 rates spread 21-55M run to run), so ``reps``
    # pairs run back to back in the steady state and the MEDIAN rate
    # is reported.  With rounds_lo=0 a single lat-corrected chain is
    # used instead (cheap smoke runs).
    rlo = max(rounds_lo, 0)
    n_pre = reps * (rlo + rounds) if rlo else rounds
    with obsspans.span(tracer, "bench.pregen_arrivals", "host_prep"):
        pre = [draw() for _ in range(n_pre)]
        jax.block_until_ready(pre)
        # stream mode uploads each chunk's draws as one [c, N] block;
        # stacking is load-generator work, pre-paid like the draws --
        # and the per-round list is then DEAD on the stream path, so
        # drop it rather than carry a second full copy of the draws
        # (83 MB at the cfg4 shape) through the timed chains
        pre_all = None
        if stream_on:
            pre_all = jax.block_until_ready(jnp.stack(pre))
            pre = None
    if stream_on:
        # AOT-compile every chunk length the timed chains will use,
        # BEFORE the timing window opens (chain lengths split into
        # stream_chunk-sized launches plus one remainder each)
        lens = set()
        for L in ((rlo, rounds) if rlo else (rounds,)):
            if L >= stream_chunk:
                lens.add(stream_chunk)
            if L % stream_chunk:
                lens.add(L % stream_chunk)
        for c in sorted(lens):
            chunk_run(c)

    met_acc = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
    if slo:
        # calibration rescaled the reservation floors on device:
        # re-register every contract from the device-truth inverse
        # arrays (the supervisor's register_from_inv discipline), so
        # the timed windows judge delivered-vs-ENFORCED contract
        slo_plane.register_from_inv(state.resv_inv, state.weight_inv,
                                    state.limit_inv)
    # calibration's warm-up serves pollute the distribution: reset the
    # telemetry accumulators so the reported percentiles cover the
    # measured steady state only (the provenance watermark re-arms at
    # the current virtual time)
    tele = tele_zero(int(t_base))
    # span window opens HERE: the summary covers the timed chains
    # only (calibration spans stay in the timeline but out of the
    # dispatch-tax decomposition)
    span_win = _span_window(tracer)
    chain_walls = []
    chain_launches = [0]
    slo_round0 = [0]

    def chain(idx):
        nonlocal state, t_base, met_acc, tele
        idx = list(idx)
        n_rounds = len(idx)
        t0 = time.perf_counter()
        counts_out, resv_out, guards, mets = [], [], [], []
        launches = 0
        if stream_on:
            # one launch per stream chunk of rounds; idx is always a
            # contiguous range here, so the pre-stacked draw block
            # slices straight onto the device
            pos = 0
            while pos < len(idx):
                c = min(stream_chunk, len(idx) - pos)
                i0 = idx[pos]
                with obsspans.span(tracer, "bench.chunk", "dispatch",
                                   rounds=c):
                    state, outs, tele = chunk_run(c)(
                        state, pre_all[i0:i0 + c],
                        jnp.int64(t_base), tele)
                    counts_out.append(outs[0])
                    guards.append(outs[1])
                    resv_out.append(outs[2])
                    mets.append(outs[3])
                t_base += c * dt_round_ns
                launches += 1
                pos += c
        else:
            for i in idx:
                with obsspans.span(tracer, "bench.round", "dispatch"):
                    state, cnt, g, resv, _, _, met_, tele = run(
                        state, pre[i], jnp.int64(t_base), tele)
                    counts_out.append(cnt)
                    resv_out.append(resv)
                    guards.append(g)
                    mets.append(met_)
                t_base += dt_round_ns
                launches += 1
        with obsspans.span(tracer, "bench.digest_sync",
                           "device_compute"):
            jax.device_get(state_digest(state))
        wall = time.perf_counter() - t0
        chain_walls.append(wall)
        chain_launches[0] += launches
        assert all(bool(jax.device_get(g).all()) for g in guards), \
            "rebase guards tripped -- counts are not trustworthy"
        # ravel: stream chunks stack per-round rows on a leading axis
        cnts = np.concatenate([np.asarray(jax.device_get(c)).ravel()
                               for c in counts_out])
        rs = np.concatenate([np.asarray(jax.device_get(r)).ravel()
                             for r in resv_out])
        # metrics ride the same round outputs, fetched untimed
        met_rows = [row for mv in mets
                    for row in np.atleast_2d(np.asarray(
                        jax.device_get(mv), dtype=np.int64))]
        met_acc = obsdev_np_combine(met_acc, *met_rows)
        if slo:
            # one timed chain = one conformance window: roll the block
            # UNTIMED (wall is already banked above), judge it, and
            # re-arm a fresh stamped block as the next chain's carry
            fresh, closed = slo_plane.roll(
                tele[-1], slo_round0[0], slo_round0[0] + n_rounds,
                skip_idle=True)
            slo_round0[0] += n_rounds
            slo_eval.observe_roll(closed)
            tele = tele[:-1] + (fresh,)
        return int(cnts.sum()), wall, cnts, rs

    if rlo:
        lat = scalar_latency()
        rates, all_cnts, all_rs, total = [], [], [], 0
        pos = 0
        for _ in range(max(reps, 1)):
            d_lo, t_lo, cnts_lo, rs_lo = chain(range(pos, pos + rlo))
            d_hi, t_hi, cnts_hi, rs_hi = chain(
                range(pos + rlo, pos + rlo + rounds))
            pos += rlo + rounds
            total += d_lo + d_hi
            all_cnts += [cnts_lo, cnts_hi]
            all_rs += [rs_lo, rs_hi]
            if t_hi <= t_lo or t_lo < 1.2 * lat:
                # jitter-inverted, or the lo chain sat at the host
                # RTT floor (wall = max(device, RTT)): the difference
                # would divide by a truncated delta
                continue
            rates.append((d_hi - d_lo) / (t_hi - t_lo))
        assert rates, \
            "no valid pair: chains too short for the host RTT floor"
        dps = float(np.median(rates))
        cnts = np.concatenate(all_cnts)
        rs = np.concatenate(all_rs)
        denom = n_pre * m * _per_pass_cap(n, k, calendar_steps,
                                          calendar_impl, ladder_levels)
    else:
        lat = scalar_latency()
        d_hi, t_hi, cnts, rs = chain(range(rounds))
        dps = d_hi / (t_hi - lat)
        total = d_hi
        denom = rounds * m * _per_pass_cap(n, k, calendar_steps,
                                           calendar_impl, ladder_levels)

    resv_frac = float(rs.sum()) / max(cnts.sum(), 1)
    mean_depth = float(np.asarray(state.depth).mean())
    out = {"dps": dps, "decisions": total,
           "fill": total / denom,
           "resv_phase_frac": resv_frac,
           "mean_depth": mean_depth,
           "select_impl": select_impl,
           "engine_loop": engine_loop,
           # part of the bench_guard series identity: a
           # provenance-off session's rates must never enter (or be
           # judged against) provenance-on medians
           "provenance_on": bool(provenance),
           "cost_analysis": cost_attr}
    # launches-per-decision is the streaming loop's acceptance
    # currency (ROADMAP #1): decisions_per_launch counts the TIMED
    # chains' device launches only, so round vs stream compare the
    # same measured region
    out["decisions_per_launch"] = total / max(chain_launches[0], 1)
    if stream_on:
        out["stream_chunk"] = stream_chunk
    sp = _span_summary(tracer, span_win, sum(chain_walls),
                       chain_launches[0])
    if sp is not None:
        out["spans"] = sp
        # scalars ride the history record as their own bench_guard
        # series (a dispatch-tax regression is a structural
        # regression even when dec/s holds)
        out["dispatch_ms_per_launch"] = sp["dispatch_ms_per_launch"]
        out["host_overhead_frac"] = sp["host_overhead_frac"]
        # per-decision amortized dispatch: what one decision pays in
        # dispatch tax when a single launch covers a whole stream
        # chunk (docs/OBSERVABILITY.md)
        sp["decisions_per_launch"] = out["decisions_per_launch"]
        out["dispatch_ns_per_decision"] = sp["dispatch_ns_per_decision"] = \
            sp["dispatch_ms_per_launch"] * 1e6 \
            / max(out["decisions_per_launch"], 1e-9)
    if calendar_steps:
        # decisions per device launch (pass = one calendar batch):
        # the bucketed-vs-minstop acceptance currency -- the ladder's
        # whole point is committing more per pass on skewed stops
        n_passes = n_pre * m
        out["calendar_impl"] = calendar_impl
        out["decisions_per_pass"] = total / max(n_passes, 1)
        if calendar_impl in ("bucketed", "wheel"):
            out["ladder_levels"] = ladder_levels
        if calendar_impl == "wheel":
            out["wheel_kernel"] = wheel_kernel
    if with_metrics:
        md = obsdev.metrics_dict(met_acc)
        out["device_metrics"] = md
        # what bounded this run (the ROADMAP limit-stall item): the
        # device counters separate the cases a bare rate cannot --
        #  - limit_stalls > 0: batches committed NOTHING while work sat
        #    queued (every head capped by its limit/reservation tag):
        #    the SCHEDULER stalled;
        #  - drained queues with zero admission drops: arrivals (the
        #    waves cap / lambda calibration) bounded the decisions --
        #    the LOAD GENERATOR capped the run, the engine had slack;
        #  - otherwise the backlog held and the engine's own
        #    throughput is the binding constraint (drops > 0 means the
        #    generator pushed past ring headroom -- engine-bound too).
        stalls = md.get("limit_stalls", 0)
        drops = md.get("ingest_drops", 0)
        if stalls:
            out["bounded_by"] = "scheduler_stalled"
        elif mean_depth < 0.75 * depth0 and not drops:
            out["bounded_by"] = "load_generator_capped"
        else:
            out["bounded_by"] = "engine_throughput"

    if conformance_rounds:
        # end-of-run per-client QoS conformance: a few extra UNTIMED
        # rounds fetch the per-client served counts (the calendar
        # served vector, or slot/length scatter otherwise), and the
        # delivered per-client rate is judged against the reservation
        # floor and the weight share of the surplus -- the sim
        # harness's table (SimReport.conformance), at bench scale
        served_c = np.zeros(n, dtype=np.int64)
        for _ in range(conformance_rounds):
            with obsspans.span(tracer, "bench.round", "dispatch"):
                state, _c, _g, _r, slot, lens, _m, tele = run(
                    state, draw(), jnp.int64(t_base), tele)
            t_base += dt_round_ns
            if calendar_steps:
                served_c += jax.device_get(slot).astype(np.int64)
            else:
                slots = jax.device_get(slot).ravel()
                ln = jax.device_get(lens).ravel()
                ok = slots >= 0
                np.add.at(served_c, slots[ok], ln[ok])
        window_s = conformance_rounds * dt_round_ns / 1e9
        rate_c = served_c / window_s
        total_rate = rate_c.sum()
        has_resv = resv_rates > 0
        resv_met = rate_c >= 0.95 * resv_rates
        surplus = max(total_rate - float(resv_rates.sum()), 0.0)
        w_share = np.where(weights.sum() > 0,
                           weights / max(weights.sum(), 1e-12), 0.0)
        expect = resv_rates + surplus * w_share
        has_w = weights > 0
        share_err = np.abs(rate_c - expect) / np.maximum(expect, 1e-9)
        out["conformance"] = {
            "window_s": window_s,
            "clients": int(n),
            "resv_clients": int(has_resv.sum()),
            "resv_met_frac": float(resv_met[has_resv].mean())
            if has_resv.any() else 1.0,
            "share_err_mean": float(share_err[has_w].mean())
            if has_w.any() else 0.0,
            "delivered_rate_total": float(total_rate),
        }
        if conformance_out:
            # telemetry must never eat the measurement: a bad path
            # here would crash AFTER the full run and lose the JSON
            # line main()'s emit() guarantees
            try:
                with open(conformance_out, "w") as fh:
                    for i in range(n):
                        fh.write(json.dumps({
                            "client": i,
                            "reservation": float(resv_rates[i]),
                            "weight": float(weights[i]),
                            "ops": int(served_c[i]),
                            "rate": float(rate_c[i]),
                            "expected_rate": float(expect[i]),
                            "resv_met": bool(resv_met[i])
                            if has_resv[i] else True,
                        }) + "\n")
            except OSError as e:
                print(f"# conformance-out write failed: {e}",
                      file=__import__("sys").stderr)

    if latency_rounds:
        # MEASURED per-round latency percentiles.  A decision's latency
        # is bounded by the wall time of the round it rides in.  A
        # window of W rounds stays in flight; device_get on round i's
        # commit counts returns when round i completes, so successive
        # return times sample each round's true completion interval
        # while the full pipeline hides the host round-trip
        # (W * round_time >> RTT).  Only intervals recorded while the
        # window was full count -- the drain tail would measure RTT,
        # not device work.
        from collections import deque

        from profile_util import scalar_latency

        # window size: enough rounds in flight that the host
        # round-trip of each device_get is hidden by device progress
        # (w * round_time > ~2x RTT); otherwise the marks would sample
        # the RTT, not the rounds
        lat_rt = scalar_latency()
        # device-side seconds per round, from the differenced median
        round_est = (total / max(n_pre, 1)) / max(dps, 1.0)
        w = max(4, int(np.ceil(2.0 * lat_rt / max(round_est, 1e-4))))
        w = min(w, max(latency_rounds // 4, 4))
        n_rounds = latency_rounds + w
        pre2 = [draw() for _ in range(n_rounds)]
        jax.block_until_ready(pre2)
        pending: deque = deque()
        marks = []
        for i in range(n_rounds):
            with obsspans.span(tracer, "bench.round", "dispatch"):
                state, cnt, _, _, _, _, _, tele = run(
                    state, pre2[i], jnp.int64(t_base), tele)
            t_base += dt_round_ns
            pending.append(cnt)
            if len(pending) >= w:
                jax.device_get(pending.popleft())
                marks.append(time.perf_counter())
        while pending:                   # drain untimed
            jax.device_get(pending.popleft())
        samples_ms = np.diff(np.asarray(marks)) * 1e3
        out["latency_samples"] = int(samples_ms.size)
        out["latency_window"] = w
        # MEASURED percentiles of per-round completion intervals.
        # Every device_get pays the host round-trip regardless of
        # readiness, so when the true round time is below it, the
        # samples floor at the RTT: the percentiles are honest
        # host-inclusive UPPER BOUNDS on round latency.
        # round_ms_mean is the differenced-chain device-side mean.
        out["round_ms_p50"] = float(np.percentile(samples_ms, 50))
        out["round_ms_p99"] = float(np.percentile(samples_ms, 99))
        out["round_ms_mean"] = round_est * 1e3

    if slo:
        # the windowed-conformance verdict of the timed chains: a
        # chain-per-window series judged by the burn-rate evaluator
        # (docs/OBSERVABILITY.md "SLO plane")
        _slo_result_block(out, slo_eval)

    if telemetry:
        # ONE untimed fetch of the device accumulators (steady-state
        # rounds only; calibration was excluded by the reset above).
        # p50/p90/p99 come from the log2 reservation-tardiness
        # histogram (upper-bound-of-bucket, so never under-reported);
        # max/mean cross the per-client ledger -- the device-truth
        # replacement for the sims' host-side recomputation.
        h_np = np.asarray(jax.device_get(tele[0]), dtype=np.int64)
        led_np = np.asarray(jax.device_get(tele[1]), dtype=np.int64)
        lt = obshist.ledger_totals(led_np)
        for q, key in ((0.50, "tardiness_p50_ns"),
                       (0.90, "tardiness_p90_ns"),
                       (0.99, "tardiness_p99_ns")):
            out[key] = obshist.hist_percentile(
                h_np, obshist.HIST_RESV_TARDINESS, q)
        out["tardiness_mean_ns"] = obshist.hist_mean(
            h_np, obshist.HIST_RESV_TARDINESS)
        out["tardiness_max_ns"] = float(lt["tardiness_max_ns"])
        out["telemetry"] = {"histograms": obshist.hist_dict(h_np),
                            "ledger_totals": lt}
        out["_hist_block"] = h_np.tolist()   # registry feed; stripped
        #                                      by main before emit
    if provenance:
        # ONE untimed fetch of the provenance block (the telemetry
        # drain discipline): margin percentiles from the on-device
        # log2 histogram, the limit-gate share, the starvation
        # watermark -- the "why" scalars next to the "what" ones
        prov_f = tele[2 if telemetry else 0]
        pd = obsprov.prov_dict(prov_f)
        out["provenance"] = pd
        out["margin_p50_ns"] = pd["margin_p50_ns"]
        out["margin_p99_ns"] = pd["margin_p99_ns"]
        out["starvation_max_ns"] = pd["starvation_max_ns"]
        out["limit_gate_share"] = round(pd["limit_gate_share"], 4)
        # once-per-episode client_starved warnings through the PR-7
        # watchdog external-warning hook (or stderr): a backlogged
        # client unserved for > 8 rounds of virtual time at the end
        # of the measured region is starving RIGHT NOW
        mon = obsprov.StarvationMonitor(8 * dt_round_ns,
                                        watchdog=watchdog)
        mon.observe(prov_f, int(t_base), backlog=state.depth)
        if mon.fired:
            out["starved_clients"] = mon.fired[:8]
    _capacity_row(out, cap_cfg, cp0)
    return out


def bench_frontier(points=((2, 64), (3, 64), (6, 64), (12, 64)), *,
                   n: int = 100_000, dt_round_ns: int = 50_000_000,
                   target_latency_ms: float = 0.0):
    """Throughput/latency frontier for the cfg4 calendar workload.

    A decision's latency is bounded by the round it rides in, and the
    round's device time scales with its batch count m -- so sweeping m
    at fixed per-batch depth traces the frontier.  Each point reports
    the differenced-chain dec/s, the device-side mean round time, and
    windowed per-round completion-interval percentiles (device-bound
    once W rounds in flight amortize the host round-trip; the
    floor of the method is RTT/W per interval).

    With ``target_latency_ms`` the sweep instead returns the
    highest-throughput point whose device-side mean round time fits
    the budget (the --target-latency mode).
    """
    rows = []
    for m, steps in points:
        r = bench_sustained(
            n, 0, m, 24, zipf=True, resv_rate=1200.0,
            dt_round_ns=dt_round_ns, waves=64, rounds_lo=8,
            latency_rounds=60, calendar_steps=steps,
            target_resv_share=0.5, reps=2)
        rows.append({"m": m, "steps": steps,
                     "dps": r["dps"],
                     "round_ms_mean": r.get("round_ms_mean", 0.0),
                     "round_ms_p50": r.get("round_ms_p50", 0.0),
                     "round_ms_p99": r.get("round_ms_p99", 0.0),
                     "resv_phase_frac": r["resv_phase_frac"],
                     "decisions": r["decisions"]})
        import sys
        print(f"# frontier m={m} steps={steps}: "
              f"{r['dps']/1e6:.1f}M dec/s, round mean "
              f"{r.get('round_ms_mean', 0):.1f}ms, interval p99 "
              f"{r.get('round_ms_p99', 0):.1f}ms", file=sys.stderr)
    if target_latency_ms:
        # an operating point only counts if it holds the workload's
        # defining 0.50 constraint share (+-0.1): a resv-saturated or
        # off-mix point's throughput is a different workload's number
        fits = [x for x in rows
                if x["round_ms_mean"] <= target_latency_ms
                and abs(x["resv_phase_frac"] - 0.5) <= 0.1]
        pick = max(fits, key=lambda x: x["dps"]) if fits else \
            min((x for x in rows
                 if abs(x["resv_phase_frac"] - 0.5) <= 0.1),
                key=lambda x: x["round_ms_mean"], default=rows[0])
        pick = dict(pick)
        pick["met_budget"] = bool(fits)
        return pick, rows
    return None, rows


def bench_churn(scenario: str = "flash_crowd", *,
                total_ids: int = 4096, epochs: int = 64,
                every: int = 4, engine: str = "prefix", m: int = 4,
                k: int = 256, ring: int = 32, waves: int = 8,
                base_lam: float = 2.0, dt_epoch_ns: int = 50_000_000,
                seed: int = 11, boost_client: int = None,
                boost_factor: float = 8.0, slo: bool = False,
                tracer=None) -> dict:
    """Open-population churn workload (docs/LIFECYCLE.md): the
    lifecycle plane drives a ``lifecycle.churn`` scenario -- flash
    crowds arriving and departing, idle eviction recycling slots,
    grow-on-demand capacity, periodic compaction -- over a sustained
    ingest+serve epoch loop, with the admin control API mounted on a
    live scrape endpoint.

    The control-plane acceptance demo rides in: at the halfway
    boundary the bench issues a REAL ``PUT /clients/{id}/qos`` over
    HTTP boosting ``boost_client``'s weight by ``boost_factor``; the
    per-client conformance table reports delivered throughput shares
    in the windows before and after, so the live update's effect is
    visible in the output (weight share up ~boost_factor among its
    weight class).  Population size is dynamic, so the row records
    peak/live client counts next to the rate (bench_guard keys the
    series by scenario + total_ids)."""
    import urllib.request

    from dmclock_tpu.engine import stream as stream_mod
    from dmclock_tpu.engine.state import init_state
    from dmclock_tpu.lifecycle import churn as churn_mod
    from dmclock_tpu.lifecycle import make_spec
    from dmclock_tpu.lifecycle.api import mount_admin_api
    from dmclock_tpu.lifecycle.plane import LifecyclePlane
    from dmclock_tpu.obs import histograms as obshist
    from dmclock_tpu.obs.registry import (MetricsHTTPServer,
                                          MetricsRegistry)
    from dmclock_tpu.robust.guarded import run_epoch_guarded

    spec = make_spec(scenario, total_ids=total_ids, seed=seed,
                     base_lam=base_lam, compact_every=2)
    from dmclock_tpu.obs import compile_plane as _cplane

    cp0 = _cplane.plane().totals()
    plane = LifecyclePlane(spec, tracer=tracer)
    state = init_state(spec["capacity0"], ring)
    hists = obshist.hist_zero()
    ledger = obshist.ledger_zero(spec["capacity0"])
    # the SLO plane rides the churn loop exactly as in the supervisor:
    # window rolls on the lifecycle boundary grid, contract epochs
    # bumped by the plane's REGISTER/UPDATE/EVICT -- the live-PUT demo
    # below lands in a FRESH contract epoch's windows (no smearing)
    slo_block = slo_plane = slo_eval = None
    slo_w0 = 0
    if slo:
        from dmclock_tpu.obs import slo as obsslo
        from dmclock_tpu.obs.alerts import SloEvaluator
        slo_plane = obsslo.SloPlane(spec["capacity0"],
                                    dt_epoch_ns=dt_epoch_ns,
                                    ring_depth=max(epochs // every, 8))
        slo_eval = SloEvaluator(slo_plane, log=lambda _line: None)
        slo_block = obsslo.window_zero(spec["capacity0"])
        plane.attach_slo(slo_plane)
    ingest = stream_mod.jit_ingest_step(dt_epoch_ns=dt_epoch_ns,
                                        waves=waves)
    rng = np.random.Generator(np.random.PCG64(seed))
    boost_at = max((epochs // 2 // every) * every, every)

    def ops_by_cid(led) -> np.ndarray:
        """Cumulative delivered ops per CLIENT ID (the ledger is
        per-slot; evicted clients are out of scope for the shares)."""
        col = np.asarray(jax.device_get(led))[:, obshist.LED_OPS]
        return plane.slots.scatter_by_cid(col, total_ids)

    # ephemeral control endpoint for the live-PUT demo (fail-soft:
    # a refused bind downgrades to the in-process handler -- the
    # workload must not die on a busy box)
    server = None
    try:
        server = MetricsHTTPServer(MetricsRegistry(), port=0)
    except OSError:
        pass
    api = mount_admin_api(server, plane, slo=slo_plane) \
        if server is not None else None

    def live_put(cid: int, r: float, w: float, l: float,
                 apply_at: int) -> bool:
        body = json.dumps({"reservation": r, "weight": w, "limit": l,
                           "apply_at": apply_at}).encode()
        if server is not None:
            req = urllib.request.Request(
                f"http://{server.host}:{server.port}/clients/{cid}/qos",
                data=body, method="PUT")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 202, resp.status
            return True
        plane.accept({"op": "update", "cid": cid, "r": r, "w": w,
                      "l": l, "apply_at": apply_at})
        return False

    decisions = 0
    ops_mid = None
    boosted = None
    t0 = time.perf_counter()
    try:
        for e in range(epochs):
            if e % every == 0:
                if slo_plane is not None and e > 0:
                    slo_block, closed = slo_plane.roll(
                        slo_block, slo_w0, e,
                        cid_of_slot=plane.slots.cid_of_slot,
                        depth=state.depth)
                    slo_w0 = e
                    slo_eval.observe_roll(closed)
                if e == boost_at:
                    if boost_client is None or \
                            boost_client not in plane.qos:
                        # lowest LIVE client id: churn scenarios may
                        # have evicted any fixed pick by now
                        boost_client = min(plane.slots.slot_of)
                    r0, w0, l0 = plane.qos[boost_client]
                    boosted = {"client": boost_client,
                               "weight_before": w0,
                               "weight_after": w0 * boost_factor,
                               "boundary": e,
                               "http": live_put(
                                   boost_client, r0,
                                   w0 * boost_factor, l0, e)}
                    ops_mid = ops_by_cid(ledger)
                with obsspans.span(tracer, "lifecycle.boundary",
                                   "host_prep", epoch=e):
                    if slo_block is not None:
                        state, ledger, slo_block = plane.boundary(
                            state, e, every, ledger=ledger,
                            slo_block=slo_block)
                    else:
                        state, ledger = plane.boundary(
                            state, e, every, ledger=ledger)
            t_base = e * dt_epoch_ns
            raw = rng.poisson(churn_mod.lam_vector(spec, e)) \
                .astype(np.int32)
            with obsspans.span(tracer, "bench.round", "dispatch"):
                state = ingest(state,
                               jnp.asarray(plane.map_counts(raw)),
                               jnp.int64(t_base))
                ep = run_epoch_guarded(
                    state, t_base + dt_epoch_ns, engine=engine, m=m,
                    k=k, with_metrics=True, hists=hists,
                    ledger=ledger, slo=slo_block, tracer=tracer)
            state, hists, ledger = ep.state, ep.hists, ep.ledger
            if slo_block is not None:
                slo_block = ep.slo
            decisions += ep.count
        jax.block_until_ready(state.depth)
        wall_s = time.perf_counter() - t0
        if slo_plane is not None:
            slo_block, closed = slo_plane.roll(
                slo_block, slo_w0, epochs,
                cid_of_slot=plane.slots.cid_of_slot,
                depth=state.depth)
            slo_eval.observe_roll(closed)
        ops_end = ops_by_cid(ledger)
    finally:
        if server is not None:
            server.close()

    # conformance: delivered throughput shares in the windows before
    # and after the live update, within the clients holding work both
    # windows -- the visible-effect gate for PUT /clients/{id}/qos.
    # A run too short to reach the boost boundary (epochs <= every)
    # skips the demo instead of crashing on the never-taken branch.
    conf = None
    if boosted is not None:
        before = ops_mid
        # clamp: a client evicted after the boost has its cumulative
        # row folded into the departed report and zeroed, so
        # end - mid can go negative for it; its after-window share is
        # simply zero
        after = np.maximum(ops_end - ops_mid, 0)
        sb, sa = max(before.sum(), 1), max(after.sum(), 1)
        bc = boost_client
        rows = sorted(set(range(min(6, total_ids))) | {bc})
        conf = [{"client": c,
                 "weight": plane.qos.get(c, (0.0, 0.0, 0.0))[1],
                 "ops_before": int(before[c]),
                 "ops_after": int(after[c]),
                 "share_before": float(before[c] / sb),
                 "share_after": float(after[c] / sa)} for c in rows]
        boosted["share_before"] = float(before[bc] / sb)
        boosted["share_after"] = float(after[bc] / sa)
        boosted["share_gain"] = boosted["share_after"] \
            / max(boosted["share_before"], 1e-12)

    snap = plane.snapshot()
    h_np = np.asarray(jax.device_get(hists), dtype=np.int64)
    out = {"dps": decisions / max(wall_s, 1e-9),
           "decisions": decisions, "wall_s": wall_s,
           "scenario": scenario, "engine": engine,
           "total_ids": total_ids, "epochs": epochs,
           "boundary_every": every,
           "peak_clients": snap["peak_clients"],
           "live_clients": snap["live_clients"],
           "capacity": snap["capacity"],
           "registrations": snap["registrations"],
           "evictions": snap["evictions"],
           "compactions": snap["compactions"],
           "qos_updates": snap["qos_updates"],
           "slot_recycles": snap["slot_recycles"],
           "grows": snap["grows"],
           "boost": boosted, "conformance": conf}
    for q, key in ((0.50, "tardiness_p50_ns"),
                   (0.90, "tardiness_p90_ns"),
                   (0.99, "tardiness_p99_ns")):
        out[key] = obshist.hist_percentile(
            h_np, obshist.HIST_RESV_TARDINESS, q)
    out["tardiness_mean_ns"] = obshist.hist_mean(
        h_np, obshist.HIST_RESV_TARDINESS)
    out["tardiness_max_ns"] = float(obshist.ledger_totals(
        np.asarray(jax.device_get(ledger),
                   dtype=np.int64))["tardiness_max_ns"])
    if slo_plane is not None:
        _slo_result_block(out, slo_eval)
        if boosted is not None:
            # the no-smearing demo: the boosted client's closed
            # windows report against their OWN contract versions --
            # the live PUT lands in a fresh contract epoch
            out["slo_boost_windows"] = [
                {"window": [w.e0, w.e1],
                 "contract_epoch": w.cepoch, "ops": w.ops}
                for w in slo_plane.ring_rows(boost_client)]
    out["_hist_block"] = h_np.tolist()
    # capacity record: the open population's projection is sized for
    # the full scripted id space landing at once (the conservative
    # per-shard planning number), lifecycle slot map included
    _capacity_row(out, dict(n=total_ids, ring=ring, engine=engine,
                            m=m, k=k, telemetry=True, slo=slo,
                            lifecycle=True), cp0)
    return out


def plan_mesh_shards(clients: int, n_shards=None, *,
                     ring: int = 16, engine: str = "prefix",
                     m: int = 4, k: int = 256,
                     telemetry: bool = True, slo: bool = True,
                     stream_chunk: int = 8) -> dict:
    """Shard planning for ``--mode mesh``: when ``--n-shards`` is not
    given, the count FALLS OUT of the client target by inverting the
    capacity plane's HBM ledger (``obs.capacity.plan_capacity`` over
    ``device_hbm_budget()`` -- the ROADMAP rule: never guessed on
    silicon).  Returns the plan record the JSON line carries:
    ``shards_planned`` (None when no budget is detectable), the
    effective shard count, per-shard clients, and
    ``projected_hbm_bytes_per_shard``.  More shards requested or
    planned than there are attached devices is an error."""
    from dmclock_tpu.obs import capacity as obscap

    cap_cfg = dict(ring=ring, engine=engine, m=m, k=k,
                   telemetry=telemetry, slo=slo,
                   stream_chunk=stream_chunk)
    budget = obscap.device_hbm_budget()
    shards_planned = None
    max_per_shard = None
    if budget is not None:
        plan = obscap.plan_capacity(budget, **cap_cfg)
        max_per_shard = max(int(plan["max_clients"]), 1)
        shards_planned = max(1, -(-int(clients) // max_per_shard))
    n_dev = len(jax.devices())
    eff = int(n_shards) if n_shards else (shards_planned or n_dev)
    if eff > n_dev:
        raise ValueError(f"mesh: {eff} shards requested/planned but "
                         f"only {n_dev} devices attached")
    per_shard = -(-int(clients) // eff)
    plan = {
        "clients_total": int(clients),
        "n_shards": eff,
        "clients_per_shard": per_shard,
        "shards_planned": shards_planned,
        "max_clients_per_shard": max_per_shard,
        "hbm_budget_bytes": budget,
        "projected_hbm_bytes_per_shard":
            int(obscap.projected_hbm(per_shard, **cap_cfg)),
    }
    # an explicit --n-shards can leave the per-shard partition over
    # the budget the planner inverted: surface it so bench_mesh can
    # apply the PR-11 capacity-gate discipline (warn + skip, never OOM)
    if max_per_shard is not None and per_shard > max_per_shard:
        plan["over_budget"] = True
    return plan


def bench_mesh(clients: int = 100_000, *, n_shards=None,
               counter_sync_every: int = 1, engine: str = "prefix",
               epochs: int = 24, warmup_epochs: int = 8,
               chunk: int = 8, m: int = 4, k: int = 256,
               ring: int = 16, depth: int = 12,
               arrival_lam: float = 2.0, waves: int = 4,
               dt_epoch_ns: int = 10 ** 8,
               with_metrics: bool = True, slo: bool = True,
               tracer=None, fault_spec=None) -> dict:
    """The mesh serving plane's aggregate-throughput trajectory
    (docs/ENGINE.md "Mesh serving"; the MULTICHIP v2 record shape):
    S full per-device engines -- each one server owning a DISTINCT
    ``clients/S``-client partition with its own queue state and
    Poisson arrival stream, so ``clients`` total contracts live
    across the mesh -- advance whole chunks of fused ingest+serve
    epochs inside ONE shard_map launch per chunk, exchanging only the
    [clients/S]-sized delta/rho counter psum at epoch boundaries
    (views refresh on the ``counter_sync_every`` grid).  On CPU
    (forced host devices) this proves the SCALING SHAPE; the silicon
    campaign inherits it as the >=100M dec/s @ 1M clients one-command
    repro.

    ``fault_spec`` (a parsed ``robust.faults.parse_fault_spec``
    dict) turns the session into a CHAOS run: a deterministic
    FaultPlan over every (warmup + timed) epoch is compiled INTO the
    fused chunks, and the row records the plan tag plus the
    per-shard dropout/resync counts read off the device metric rows
    (cross-checked against the plan oracle by the CI mesh chaos
    smoke).  Chaos rows never enter bench_guard's clean-run
    medians."""
    import dataclasses

    from dmclock_tpu.obs import device as obsdev
    from dmclock_tpu.obs import slo as obsslo
    from dmclock_tpu.parallel import mesh as mesh_mod
    from dmclock_tpu.parallel import tracker as trk
    from dmclock_tpu.robust import faults as faults_mod
    from dmclock_tpu.robust.supervisor import EpochJob, _job_state

    plan = plan_mesh_shards(clients, n_shards, ring=ring,
                            engine=engine, m=m, k=k, slo=slo,
                            stream_chunk=chunk)
    S = plan["n_shards"]
    n = plan["clients_per_shard"]
    if plan.pop("over_budget", False):
        # the capacity-gate discipline (PR-11): a partition the
        # planner's own inversion says exceeds the per-device budget
        # is warned + skipped with a tagged row, never launched into
        # an OOM mid-session
        import sys as _sys

        print(f"# mesh: SKIPPED -- {n} clients/shard exceeds the "
              f"planned {plan['max_clients_per_shard']} for the "
              f"detected budget even at the device-capped {S} "
              "shards; lower --clients or attach more devices",
              file=_sys.stderr)
        return {"workload": "mesh", "engine": engine,
                "engine_loop": "mesh", "dps": 0.0, "decisions": 0,
                "capacity_skipped": True,
                "projected_hbm_bytes":
                    plan["projected_hbm_bytes_per_shard"],
                "counter_sync_every":
                    int(max(counter_sync_every, 1)),
                **{key: val for key, val in plan.items()
                   if val is not None}}
    job = EpochJob(engine=engine, engine_loop="mesh", n_shards=S,
                   counter_sync_every=counter_sync_every, n=n,
                   depth=depth, ring=ring, m=m, k=k,
                   arrival_lam=arrival_lam, waves=waves,
                   dt_epoch_ns=dt_epoch_ns)
    mesh = mesh_mod.make_mesh(S)
    state = mesh_mod.stack_shards(
        _job_state(dataclasses.replace(job, engine_loop="stream")),
        S, mesh)
    cd, cr, vd, vr = mesh_mod.counter_init(S, n)
    wblock = mesh_mod.stack_shards(obsslo.window_zero(n), S, mesh)
    warm_chunks = max(1, warmup_epochs // chunk)
    n_chunks = max(1, epochs // chunk)
    fplan = None
    if fault_spec is not None:
        # one deterministic plan over EVERY epoch the session runs
        # (warmup included: a chaos session is chaotic end to end)
        fplan = faults_mod.plan_from_spec(
            fault_spec, (warm_chunks + n_chunks) * chunk, S)
    # chunks launch at e0 = multiples of chunk, so chunk % K == 0
    # keeps every group head on the sync grid and the grouped
    # (collective-free non-sync epoch) program stays bit-identical
    every = int(max(counter_sync_every, 1))
    skipping = fplan is None and every > 1 and chunk % every == 0
    fn = mesh_mod.jit_mesh_chunk(
        mesh, engine=engine, epochs=chunk, m=m, k=k,
        dt_epoch_ns=dt_epoch_ns, waves=waves,
        with_metrics=with_metrics,
        counter_sync_every=counter_sync_every, ingest=True,
        with_faults=fplan is not None,
        collective_skipping=skipping)
    rng = np.random.Generator(np.random.PCG64(29))

    def draw(e):
        return jnp.asarray(np.swapaxes(np.stack(
            [rng.poisson(arrival_lam, (S, n)).astype(np.int32)
             for _ in range(e)]), 0, 1))

    fault_mets = []

    def fault_chunk(e0):
        # sliced + device-resident BEFORE any timed launch (see the
        # pregen discipline below): the timed loop must not pay
        # host-side mask slicing or H2D transfers per chunk
        if fplan is None:
            return None
        fc = faults_mod.plan_chunk(fplan, e0, e0 + chunk)
        return tuple(jnp.asarray(a) for a in fc)

    def launch(out, e0, counts, fc):
        with obsspans.span(tracer, "mesh.bench_chunk", "dispatch",
                           epoch0=e0, shards=S,
                           chaos=fplan is not None):
            out = fn(out.state, out.cd, out.cr, out.view_d,
                     out.view_r, jnp.int64(e0), counts,
                     None, None, out.slo, None, None, fc)
        if fplan is not None:
            # per-shard fault rows ride the per-epoch metric vectors;
            # fetched untimed after the run (async-safe append)
            fault_mets.append(out.outs["metrics"])
        return out

    # warmup (covers compile + tag-transient), untimed
    out = mesh_mod.MeshChunk(state=state, outs={}, cd=cd, cr=cr,
                             view_d=vd, view_r=vr, slo=wblock)
    e0 = 0
    for _ in range(warm_chunks):
        out = launch(out, e0, draw(chunk), fault_chunk(e0))
        e0 += chunk
    jax.block_until_ready(out.state)

    # timed window: ALL raw draws AND chaos mask slices pre-generated
    # (and device-resident) before the clock starts -- the
    # every-other-bench pregen discipline; host RNG/slicing time must
    # not serialize into the async chunk chain and bias the aggregate
    # dec/s the MULTICHIP record reads -- then chain chunks
    # asynchronously, one sync at the end
    pregen = [(draw(chunk), fault_chunk(e0 + i * chunk))
              for i in range(n_chunks)]
    jax.block_until_ready([p[0] for p in pregen])
    if fplan is not None:
        jax.block_until_ready([p[1] for p in pregen])
    timed = []
    t0 = time.perf_counter()
    for counts_c, fc in pregen:
        out = launch(out, e0, counts_c, fc)
        timed.append(out.outs["count"])
        e0 += chunk
    jax.block_until_ready(out.state)
    wall = time.perf_counter() - t0

    # exact decision counts, fetched untimed; [S, E, ...] per chunk
    per_shard = np.zeros(S, dtype=np.int64)
    for counts_arr in timed:
        a = np.asarray(jax.device_get(counts_arr))
        per_shard += a.reshape(S, -1).sum(axis=1)
    total = int(per_shard.sum())
    dps = total / wall
    shard_dps = per_shard / wall
    # the timed window starts at the post-warmup GLOBAL epoch: the
    # device sync grid is epoch % K == 0, so the sync count inside
    # the window depends on where it starts
    sched = trk.exchange_schedule(n_chunks * chunk,
                                  counter_sync_every,
                                  start=warm_chunks * chunk)
    bytes_per_sync = trk.counter_view_bytes(n)
    row = {
        "workload": "mesh",
        "engine": engine,
        "engine_loop": "mesh",
        "dps": dps,
        "dps_per_shard_mean": float(shard_dps.mean()),
        "dps_per_shard_min": float(shard_dps.min()),
        "dps_per_shard_max": float(shard_dps.max()),
        "dps_per_shard": [float(x) for x in shard_dps],
        "decisions": total,
        "wall_s": wall,
        "epochs": n_chunks * chunk,
        "stream_chunk": chunk,
        "counter_sync_every": int(max(counter_sync_every, 1)),
        "counter_syncs": sched["syncs"],
        "counter_bytes_per_sync": bytes_per_sync,
        "collective_skipping": bool(skipping),
        # what the compiled program EXECUTES: with collective
        # skipping the [C]-sized psum runs once per K-epoch sync
        # group (non-sync epochs are collective-free by program
        # structure), so the per-epoch wire cost is bytes/K; the
        # flat program (chaos, or K not dividing the chunk) still
        # pays it every epoch
        "counter_bytes_per_epoch":
            float(bytes_per_sync / every if skipping
                  else bytes_per_sync),
        # view-refresh bytes amortized over the sync grid -- the
        # window-aware figure (sync count depends on where the timed
        # window starts on the epoch % K grid)
        "counter_view_bytes_per_epoch":
            bytes_per_sync * sched["syncs"] / max(sched["epochs"], 1),
        **{key: val for key, val in plan.items() if val is not None},
    }
    # chaos accounting: the plan tag + per-shard dropout/resync
    # counts read off the DEVICE metric rows (every launched chunk,
    # warmup included, so the totals equal the plan_events oracle --
    # the CI mesh chaos smoke pins the equality).  Clean sessions
    # record fault_plan="none"; bench_guard keys both the record- and
    # the row-level exclusion on it.
    row["fault_plan"] = faults_mod.describe(fplan)
    if fplan is not None:
        mets = np.zeros((S, obsdev.NUM_METRICS), dtype=np.int64)
        for mchunk in fault_mets:
            a = np.asarray(jax.device_get(mchunk), dtype=np.int64)
            for s in range(S):
                mets[s] = obsdev.metrics_combine_np(mets[s], *a[s])
        row["fault_dropouts_per_shard"] = [
            int(x) for x in mets[:, obsdev.MET_SERVER_DROPOUTS]]
        row["fault_resyncs_per_shard"] = [
            int(x) for x in mets[:, obsdev.MET_TRACKER_RESYNCS]]
        row["faults_injected_total"] = int(
            mets[:, obsdev.MET_FAULTS_INJECTED].sum())
        try:
            from dmclock_tpu.obs import default_registry
            obsdev.publish_shard_faults(
                default_registry(), mets, labels={"workload": "mesh"})
        except Exception:
            pass
    # the cluster-wide conformance table (window_mesh_reduce merge)
    # rides the scrape registry with per-shard decomposition
    try:
        from dmclock_tpu.obs import default_registry
        obsslo.publish_shard_windows(
            default_registry(), np.asarray(jax.device_get(out.slo)),
            merged=np.asarray(jax.device_get(out.slo_merged)),
            workload="mesh")
    except Exception:
        pass
    return row


def bench_controller(scenarios=("shard_skew", "limit_thrash",
                                "diurnal"), *,
                     sides: str = "both", total_ids: int = 192,
                     epochs: int = 48, ckpt_every: int = 4,
                     engine: str = "prefix",
                     engine_loop: str = "stream", m: int = 2,
                     k: int = 32, ring: int = 16, waves: int = 6,
                     seed: int = 17, tracer=None) -> dict:
    """The closed-loop controller A/B (docs/CONTROLLER.md): each
    churn scenario runs as a pair of EXACT-TWIN supervised jobs --
    identical engine, arrival stream, lifecycle spec, and SLO plane,
    differing ONLY in ``EpochJob(controller=...)`` -- so the row's
    recovered dec/s and burn-episode-duration delta are attributable
    to the controller's actuations alone (controller=off is
    bit-identical to the bare runner by the PR-18 digest gate, so
    the off side doubles as the clean reference).

    Scenarios: ``shard_skew`` (hot-shard melt; admission clamp +
    ladder pressure), ``limit_thrash`` (alternating tight limits;
    limit-break burn drives the clamp rule), and the ``diurnal``
    autoscale variant (day/night load swings; the clean-streak
    up-rules walk the knobs back out at night).  ``sides`` picks
    which twins run: "off", "on", or "both" (recovered deltas need
    both).  Wall time includes compile -- both twins pay it, and the
    row records the actuation count so a recompile-heavy trajectory
    is visible; this is a control-plane demo row, not a throughput
    record (bench_guard excludes controller-actuated sessions from
    clean medians)."""
    import dataclasses

    from dmclock_tpu.lifecycle import make_spec
    from dmclock_tpu.robust.supervisor import EpochJob, run_job

    def one(job):
        t0 = time.perf_counter()
        res = run_job(job)
        return res, time.perf_counter() - t0

    out = {}
    for scenario in scenarios:
        spec = make_spec(scenario, total_ids=total_ids,
                         capacity0=max(16, total_ids // 4),
                         seed=seed)
        job = EpochJob(engine=engine, engine_loop=engine_loop,
                       churn=spec, epochs=epochs, m=m, k=k,
                       ring=ring, waves=waves,
                       ckpt_every=ckpt_every, seed=seed,
                       with_slo=True)
        row = {"workload": "controller", "scenario": scenario,
               "engine": engine, "engine_loop": engine_loop,
               "epochs": epochs, "ckpt_every": ckpt_every,
               "total_ids": total_ids, "controller": sides}
        with obsspans.span(tracer, "controller.bench_ab",
                           "dispatch", scenario=scenario,
                           sides=sides):
            if sides == "both":
                # untimed warmup: the twins share the process-level
                # jit cache, so whoever ran FIRST would otherwise pay
                # the whole compile and hand the other twin a free
                # ride -- warm the cache on the off-config once, then
                # time both (actuation-induced retraces still land on
                # the on twin's clock; that cost is real)
                run_job(job)
            if sides in ("off", "both"):
                off, wall = one(job)
                row.update(
                    dps_off=off.decisions / wall,
                    decisions_off=int(off.decisions),
                    wall_s_off=wall,
                    violations_off=int(
                        off.slo["violations_total"]),
                    burn_windows_off=int(
                        off.slo.get("burn_windows", 0)),
                    burn_epochs_off=int(
                        off.slo.get("burn_epochs", 0)))
                if sides == "off":
                    row["slo"] = off.slo
            if sides in ("on", "both"):
                on, wall = one(
                    dataclasses.replace(job, controller=True))
                traj = on.controller_trajectory or []
                row.update(
                    dps_on=on.decisions / wall,
                    decisions_on=int(on.decisions),
                    wall_s_on=wall,
                    violations_on=int(on.slo["violations_total"]),
                    burn_windows_on=int(
                        on.slo.get("burn_windows", 0)),
                    burn_epochs_on=int(
                        on.slo.get("burn_epochs", 0)),
                    controller_decisions=int(
                        on.controller_decisions),
                    controller_knobs=on.controller_knobs,
                    controller_trajectory=traj,
                    slo=on.slo)
        # the A/B verdicts: throughput recovered and burn duration
        # shed by closing the loop (positive = controller helped)
        if sides == "both":
            row["dps"] = row["dps_on"]
            row["recovered_dps"] = row["dps_on"] - row["dps_off"]
            row["burn_epochs_recovered"] = (row["burn_epochs_off"]
                                            - row["burn_epochs_on"])
            row["violations_recovered"] = (row["violations_off"]
                                           - row["violations_on"])
        else:
            row["dps"] = row.get("dps_on", row.get("dps_off", 0.0))
        out[f"controller_{scenario}"] = row
    return out


def bench_rpc(*, workers: int = 4, requests: int = 64, n: int = 32,
              epochs: int = 16, ckpt_every: int = 2, m: int = 2,
              k: int = 32, ring: int = 16, waves: int = 6,
              seed: int = 17, engine: str = "prefix",
              fault_spec=None, tracer=None) -> dict:
    """The RPC ingest front-end leg (docs/RPC.md): a real loopback
    :class:`net.server.IngestServer`, ``workers`` concurrent
    loadgen clients driving seeded deterministic schedules over real
    sockets, the serving loop admitting the coalesced superwaves
    through the existing device clamp -- then the acceptance gate
    in-process: a self-generated replay fed the journaled
    admitted-counts trace must land on the IDENTICAL chain digest
    (``digest_match``).  ``fault_spec`` runs the leg as seeded
    network chaos with exact drop/dup/reorder accounting against
    the host oracle (``chaos_exact``).  This is a serving-plane
    demo row, not a throughput record: wall time includes socket
    round-trips and the journal's fsyncs (that cost is the point)."""
    import dataclasses
    import tempfile
    import threading

    from dmclock_tpu.net import faults as net_faults
    from dmclock_tpu.net.journal import ArrivalJournal
    from dmclock_tpu.net.serve import (RpcServeConfig, make_server,
                                       run_serve, trace_sha)
    from scripts.loadgen import full_schedule, run_worker

    scheds = full_schedule(seed, workers=workers, requests=requests,
                           n_clients=n, max_nops=3)
    spec = net_faults.parse_net_fault_spec(fault_spec)
    oracle = net_faults.plan_schedule_events(
        spec, [[(c, s) for c, s, _ in sc] for sc in scheds])
    with tempfile.TemporaryDirectory() as d:
        cfg = RpcServeConfig(
            engine=engine, n=n, epochs=epochs, ckpt_every=ckpt_every,
            m=m, k=k, ring=ring, waves=waves, seed=seed, workdir=d,
            fault_spec=fault_spec, high_watermark=10 ** 6,
            wait_ops=1, wait_timeout_s=60)
        server = make_server(cfg).start()
        threads = [threading.Thread(
            target=run_worker,
            args=("127.0.0.1", server.port, scheds[w]),
            kwargs=dict(timeout_s=0.5, max_attempts=10))
            for w in range(workers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out = run_serve(cfg, server=server)
        wall = time.perf_counter() - t0
        server.stop()
        trace = ArrivalJournal(d).counts_trace()
        replay = run_serve(dataclasses.replace(cfg, workdir=None,
                                               wait_ops=0),
                           trace=trace)
    ev = out["events"]
    chaos_exact = (ev.get("drops_injected", 0) == oracle["drops"]
                   and ev.get("dup_frames", 0) == oracle["dups"]
                   and ev.get("reordered", 0) == oracle["reorders"])
    return {"rpc": {
        "workload": "rpc",
        "scenario": net_faults.describe(spec),
        "workers": int(workers),
        "requests_per_worker": int(requests),
        "engine": engine, "epochs": epochs,
        "dps": out["decisions"] / max(wall, 1e-9),
        "decisions": out["decisions"],
        "wall_s": wall,
        "admitted_ops": out["admitted_ops_traced"],
        "carry_ops": out["carry_ops"],
        "ingest_drops": out["ingest_drops"],
        "digest": out["digest"],
        "digest_match": bool(replay["digest"] == out["digest"]
                             and replay["trace_sha"]
                             == out["trace_sha"]),
        "chaos_exact": bool(chaos_exact),
        "oracle_drops": oracle["drops"],
        "oracle_dups": oracle["dups"],
        "oracle_reorders": oracle["reorders"],
        "chaos_drops": int(ev.get("drops_injected", 0)),
        "chaos_dups": int(ev.get("dup_frames", 0)),
        "chaos_reorders": int(ev.get("reordered", 0)),
        "busy": int(ev.get("busy", 0)),
        "deduped": int(ev.get("deduped", 0)),
        "lat_p50_ms": out["latency"]["p50_ms"],
        "lat_p99_ms": out["latency"]["p99_ms"],
    }}


def bench_mesh_rebalance(*, n_shards: int = 4, total_ids: int = 64,
                         epochs: int = 24, ckpt_every: int = 4,
                         engine: str = "prefix", m: int = 2,
                         k: int = 32, ring: int = 16, waves: int = 6,
                         seed: int = 17, tracer=None) -> dict:
    """The shard-rebalancing A/B (docs/LIFECYCLE.md "Placement and
    migration"): two EXACT-TWIN supervised mesh jobs on the
    ``shard_skew`` churn scenario -- identical engine, arrival
    stream, and lifecycle spec -- differing ONLY in the placement
    plane.  The off twin is today's static ``cid % S`` mesh (no
    placement map, no controller: bit-identical to ``--rebalance
    off``); the on twin runs ``placement="p2c"`` with a controller
    whose ONLY live rule is ``migrate`` (sync pinned, clamp/compact
    thresholds parked), so the row's recovered dec/s and shard-skew
    delta are attributable to the migrations alone.

    Skew metric: max/mean of the per-shard delta-completion totals
    (``mesh_counters[0]``) at the end of the run -- 1.0 is perfectly
    level, S is everything-on-one-shard.  ``skew_before`` is the off
    twin's final skew (what the static mesh ends at), ``skew_after``
    the on twin's."""
    import dataclasses

    import jax

    from dmclock_tpu.lifecycle import make_spec
    from dmclock_tpu.robust.supervisor import EpochJob, run_job

    S = int(n_shards)
    if S > len(jax.devices()):
        raise ValueError(f"rebalance: {S} shards but only "
                         f"{len(jax.devices())} devices attached")
    spec = make_spec("shard_skew", total_ids=total_ids,
                     n_shards=S, seed=seed)
    # pick="hot": move the largest-demand DRAINED clients -- their
    # future arrivals follow them (arrival rate is a property of the
    # id, routing is a property of the placement map), so each move
    # sheds real offered load onto an idle shard's serve budget.
    # (The cold pick is the digest-twin-provable class; the bench
    # measures throughput, the tests prove equivalence.)
    ctl = dict(sync_max=1, backlog_hi=10**9, occ_lo=0.0,
               hysteresis=1, cooldown=2,
               migrate_skew_hi=1.5, migrate_pick="hot",
               migrate_max=4)
    job = EpochJob(engine=engine, engine_loop="mesh", n_shards=S,
                   churn=spec, epochs=epochs, m=m, k=k, ring=ring,
                   waves=waves, ckpt_every=ckpt_every, seed=seed)

    def one(job):
        t0 = time.perf_counter()
        res = run_job(job)
        return res, time.perf_counter() - t0

    def skew(res):
        tot = np.asarray(res.mesh_counters[0],
                         dtype=np.float64).sum(axis=1)
        return float(tot.max() / max(tot.mean(), 1e-12)), \
            [int(t) for t in tot]

    row = {"workload": "mesh_rebalance", "scenario": "shard_skew",
           "engine": engine, "engine_loop": "mesh", "n_shards": S,
           "epochs": epochs, "ckpt_every": ckpt_every,
           "total_ids": total_ids, "rebalance": "on",
           "placement": "p2c"}
    with obsspans.span(tracer, "mesh.bench_rebalance", "dispatch",
                       n_shards=S, epochs=epochs):
        run_job(job)    # untimed warmup: twins share the jit cache
        off, wall_off = one(job)
        on, wall_on = one(dataclasses.replace(
            job, placement="p2c", controller=ctl))
    skew_off, shards_off = skew(off)
    skew_on, shards_on = skew(on)
    row.update(
        dps_off=off.decisions / wall_off,
        dps_on=on.decisions / wall_on,
        decisions_off=int(off.decisions),
        decisions_on=int(on.decisions),
        wall_s_off=wall_off, wall_s_on=wall_on,
        shard_skew_before=skew_off, shard_skew_after=skew_on,
        shard_skew_final=skew_on,
        shard_decisions_off=shards_off, shard_decisions_on=shards_on,
        migrations=int(on.migrations),
        migration_log=on.migration_log,
        placement_counters=on.placement_counters,
        controller_knobs=on.controller_knobs)
    row["dps"] = row["dps_on"]
    row["recovered_dps"] = row["dps_on"] - row["dps_off"]
    # the wall-clock-free signal: completions the migrations unlocked
    # (arrivals served that the static mesh left queued on the hot
    # shard).  On a scaled cpu shape the on twin's wall time is
    # dominated by host actuation + retraces -- like
    # bench_controller, this is a control-plane demo row, and
    # recovered_decisions is the honest recovery currency there.
    row["recovered_decisions"] = (row["decisions_on"]
                                  - row["decisions_off"])
    row["shard_skew_recovered"] = skew_off - skew_on
    return row


def _with_ladder(ladder, cfg: dict, fn):
    """Run one workload under the degradation ladder
    (robust.guarded.DegradationLadder): a failed run whose config
    still has a fast path engaged (radix selection, bucketed
    calendar, tag32 carry) steps that knob down to its proven-exact
    twin and retries, instead of losing the whole session to one
    wedged fast path.  A device-side failure (XlaRuntimeError -- the
    wedged-kernel shape) IS ladder-eligible; a backend that is plainly
    dead (init/connect failure messages) re-raises, since no fast-path
    concession can revive it.  Returns (result_row, effective_cfg)."""
    import sys

    while True:
        c = ladder.apply(cfg)
        try:
            return fn(**c), c
        except (AssertionError, RuntimeError) as e:
            msg = str(e).lower()
            if isinstance(e, RuntimeError) and \
                    ("unable to initialize" in msg
                     or "failed to connect" in msg):
                raise           # dead backend, not a fast-path fault
            # device errors count as launch failures, tripped guard
            # asserts as guard trips -- same escalation either way
            stepped = ladder.note_epoch(
                c, guard_trips=int(isinstance(e, AssertionError)),
                launch_failures=int(isinstance(e, RuntimeError)))
            if not stepped:
                raise           # nothing left to concede
            step = ladder.steps[-1]
            print(f"# ladder: {step.knob} {step.from_value} -> "
                  f"{step.to_value} after {type(e).__name__}: {e}",
                  file=sys.stderr)


def main() -> None:
    import argparse
    import contextlib
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", metavar="DIR", default=None)
    ap.add_argument("--mode",
                    choices=["all", "serve", "cfg3", "cfg4",
                             "frontier", "churn", "mesh",
                             "controller", "rpc"],
                    default="all")
    ap.add_argument("--clients", type=int, default=100_000,
                    metavar="N",
                    help="--mode mesh: TOTAL client population across "
                    "all shards; without --n-shards the shard count "
                    "is derived by inverting the capacity plane's HBM "
                    "ledger (obs.capacity.plan_capacity over the "
                    "detected device budget) -- the shard count falls "
                    "out of the client target, never guessed")
    ap.add_argument("--n-shards", type=int, default=None, metavar="S",
                    help="--mode mesh: per-device engine count (more "
                    "than the attached devices is an error)")
    ap.add_argument("--counter-sync-every", type=int, default=1,
                    metavar="K",
                    help="--mode mesh: exchange the [C]-sized "
                    "delta/rho counter psum only on epochs where "
                    "epoch %% K == 0 (the staleness knob; the "
                    "paper's piggybacked views are naturally stale, "
                    "and K>1 is pinned decision-exact against the "
                    "host loop's delay_counters fault)")
    ap.add_argument("--rebalance", choices=["off", "on"],
                    default="off",
                    help="--mode mesh: 'on' adds the shard-"
                    "rebalancing A/B row (bench_mesh_rebalance; "
                    "docs/LIFECYCLE.md \"Placement and migration\"): "
                    "exact supervised twins on the shard_skew churn "
                    "scenario differing only in placement='p2c' + "
                    "the migrate controller rule, recording shard "
                    "skew before/after and the aggregate dec/s "
                    "recovered.  'off' (default) is bit-identical "
                    "to today's static mesh -- the flag adds a row, "
                    "it never perturbs the mesh series")
    ap.add_argument("--churn-scenario",
                    choices=["flash_crowd", "diurnal", "churn_storm",
                             "limit_thrash"],
                    default="flash_crowd",
                    help="open-population scenario for the churn "
                    "workload (lifecycle.churn; docs/LIFECYCLE.md): "
                    "clients register/depart through the lifecycle "
                    "plane, slots recycle, capacity grows on demand, "
                    "compaction repacks -- and a live PUT "
                    "/clients/{id}/qos lands mid-run through the "
                    "mounted admin API (its delivered-share effect "
                    "rides the conformance table).  Runs under "
                    "--mode churn or --mode all")
    ap.add_argument("--target-latency", type=float, default=0.0,
                    metavar="MS",
                    help="pick the fastest cfg4 operating point whose "
                         "device-side mean round time fits this "
                         "budget; implies --mode frontier")
    ap.add_argument("--select-impl", choices=["sort", "radix", "both"],
                    default="sort",
                    help="prefix-engine selection backend (fastpath "
                    "select_impl) for the serve/cfg3 workloads; 'both' "
                    "runs serve under each and reports serve + "
                    "serve_radix (bit-identical decisions, A/B timing; "
                    "cfg4's calendar engine is sortless and ignores "
                    "this)")
    ap.add_argument("--calendar-impl",
                    choices=["minstop", "bucketed", "wheel", "both"],
                    default="minstop",
                    help="calendar-engine commit-boundary scheme for "
                    "the cfg4 workload (fastpath calendar_impl): "
                    "'bucketed' fuses a stop-key ladder of "
                    "--ladder-levels refreshed boundaries per batch "
                    "(more decisions per pass on skewed populations); "
                    "'wheel' drives the same ladder from a maintained "
                    "timer-wheel bucket index (O(1)-bucket re-slot "
                    "per commit; --wheel-kernel picks its kernel); "
                    "'both' runs cfg4 under all three and reports "
                    "cfg4 + cfg4_bucketed + cfg4_wheel (separate "
                    "bench_guard series)")
    ap.add_argument("--ladder-levels", type=int, default=8,
                    metavar="L",
                    help="ladder levels per bucketed/wheel calendar "
                    "batch")
    ap.add_argument("--wheel-kernel", choices=["xla", "pallas"],
                    default="xla",
                    help="wheel-calendar bucket scatter/scan backend "
                    "(fastpath wheel_kernel): 'pallas' runs the "
                    "hand-written fused kernel (bit-identical to "
                    "'xla')")
    ap.add_argument("--engine-loop",
                    choices=["round", "stream", "both"],
                    default="round",
                    help="sustained-workload loop structure "
                    "(docs/ENGINE.md): 'round' = one fused "
                    "ingest+serve launch per round (the historical "
                    "shape); 'stream' = one launch per "
                    "--stream-chunk rounds (lax.scan over the "
                    "identical round body, decisions bit-identical; "
                    "launches-per-decision down by the chunk "
                    "factor); 'both' runs each sustained workload "
                    "under each and reports e.g. cfg4 + cfg4_stream "
                    "(separate bench_guard series).  serve-only has "
                    "no ingest loop and ignores this")
    ap.add_argument("--stream-chunk", type=int, default=8,
                    metavar="R",
                    help="rounds fused per stream-loop launch")
    ap.add_argument("--device-metrics", choices=["on", "off"],
                    default="on",
                    help="accumulate the on-device obs vector inside "
                    "the timed kernels (bit-identical decisions either "
                    "way; 'off' measures the metrics overhead itself)")
    ap.add_argument("--telemetry", choices=["on", "off"],
                    default="on",
                    help="accumulate the device QoS telemetry plane "
                    "(log2 histograms + per-client conformance "
                    "ledger, obs.histograms) inside the timed "
                    "sustained kernels; decisions are bit-identical "
                    "either way, and the JSON line carries "
                    "p50/p90/p99 reservation tardiness from the "
                    "device ledger ('off' measures the overhead)")
    ap.add_argument("--slo", choices=["on", "off"], default="on",
                    help="accumulate the device-resident SLO window "
                    "block (obs.slo) inside the timed sustained "
                    "rounds (donated carry, one window per timed "
                    "chain, fetched untimed) and judge it with the "
                    "burn-rate evaluator (obs.alerts); decisions are "
                    "bit-identical either way, and the JSON line "
                    "carries a per-workload 'slo' block (violation "
                    "counts, worst-window share error, p99 window "
                    "tardiness).  'off' measures the overhead")
    ap.add_argument("--provenance", choices=["on", "off"],
                    default="on",
                    help="accumulate the decision provenance plane "
                    "(obs.provenance) inside the timed sustained "
                    "rounds: per-decision winner margins, the "
                    "limit-gate state, eligible-set depth, winning "
                    "phase, and the per-client last-served "
                    "starvation watermark; decisions are "
                    "bit-identical either way, and the JSON line "
                    "carries margin_p50/p99_ns, limit_gate_share, "
                    "and starvation_max_ns ('off' measures the "
                    "overhead; provenance-off rows form their own "
                    "bench_guard series)")
    ap.add_argument("--capacity", choices=["on", "off"], default="on",
                    help="capacity plane (docs/OBSERVABILITY.md): "
                    "pre-launch projected-HBM check per sustained "
                    "workload (projection over budget -> warn + skip "
                    "the workload, never crash) and the "
                    "compile_ms_total / retraces / "
                    "projected_hbm_bytes / bound_class record in the "
                    "JSON line + history ('off' disables the gate; "
                    "the record always rides)")
    ap.add_argument("--conformance-out", metavar="FILE", default=None,
                    help="write the cfg4 per-client conformance table "
                    "as JSONL")
    ap.add_argument("--spans", action="store_true",
                    help="collect host spans (obs.spans) through "
                    "calibration + the timed chains and report the "
                    "per-launch dispatch-tax decomposition "
                    "(dispatch_ms_per_launch, device_ms_per_launch, "
                    "host_overhead_frac, per-category breakdown) in "
                    "the JSON line; decisions are bit-identical "
                    "either way (spans are host-side only)")
    ap.add_argument("--trace-out", metavar="FILE.json", default=None,
                    help="write the collected spans as a Chrome "
                    "trace-event / Perfetto timeline (implies "
                    "--spans); load in chrome://tracing")
    ap.add_argument("--metrics-port", type=int, metavar="PORT",
                    default=None,
                    help="serve the live default metrics registry over "
                    "HTTP (GET /metrics, Prometheus text) for the "
                    "duration of the bench; 0 picks an ephemeral port "
                    "(printed to stderr)")
    ap.add_argument("--fault-plan", default="none", metavar="TAG",
                    help="label this session's fault-injection plan "
                    "(robust.faults.describe() tag) in the JSON line "
                    "and the benchmark history record; bench_guard "
                    "keeps non-'none' (chaos) sessions out of the "
                    "clean-run regression medians.  With --mode mesh "
                    "a PARSEABLE spec (e.g. 'seed=7,p_dropout=0.05,"
                    "mean_outage_steps=2,p_dup=0.1,max_skew_ns=1000') "
                    "samples a real FaultPlan and compiles it INTO "
                    "the fused chunks -- the chaos mesh session; the "
                    "row then records per-shard dropout/resync "
                    "counts (docs/ROBUSTNESS.md 'Degraded-mode "
                    "mesh')")
    ap.add_argument("--controller",
                    choices=["off", "on", "both"], default="both",
                    help="--mode controller: which twin(s) of the "
                    "closed-loop controller A/B to run under the "
                    "shard_skew / limit_thrash / diurnal churn "
                    "scenarios (docs/CONTROLLER.md).  'both' (the "
                    "default) runs exact twins differing only in "
                    "EpochJob(controller=...) and reports recovered "
                    "dec/s + burn-episode-duration deltas; the "
                    "history record tags controller-actuated "
                    "sessions so bench_guard keeps them out of the "
                    "clean-run medians")
    ap.add_argument("--rpc-workers", type=int, default=4,
                    metavar="W",
                    help="--mode rpc: concurrent loadgen workers "
                    "driving the loopback ingest server (each owns a "
                    "disjoint client-id partition with a seeded, "
                    "byte-identical request schedule)")
    ap.add_argument("--rpc-fault-spec", default=None, metavar="SPEC",
                    help="--mode rpc: seeded network chaos spec "
                    "(net.faults grammar, e.g. 'seed=7,p_drop=0.1,"
                    "p_dup=0.05,p_reorder=0.05'); the row then gates "
                    "exact drop/dup/reorder accounting against the "
                    "host oracle (chaos_exact) and the session is "
                    "kept out of bench_guard's clean medians")
    ap.add_argument("--supervised", action="store_true",
                    default=os.environ.get("DMCLOCK_SUPERVISED")
                    == "1",
                    help="tag this session as running under the "
                    "robust.supervisor (set automatically via "
                    "DMCLOCK_SUPERVISED=1 in supervised "
                    "environments); with DMCLOCK_RESTARTS > 0 the "
                    "history record carries the restart count and "
                    "bench_guard keeps the run out of the clean-run "
                    "medians")
    ap.add_argument("--no-ladder", action="store_true",
                    help="disable the degradation ladder (a failed "
                    "fast-path workload raises instead of stepping "
                    "down to its exact twin and retrying)")
    args = ap.parse_args()
    enable_compile_cache()
    # every number below is a device number: no chip, no run (and no
    # JSON line -- a CPU rate must never pass for a chip rate)
    backend = jax.devices()[0].platform
    if backend != "tpu":
        sys.exit(f"bench.py: JAX found no TPU (platform {backend!r}); "
                 "this benchmark runs on the chip only")
    restarts = int(os.environ.get("DMCLOCK_RESTARTS", "0") or 0)
    if args.target_latency:
        args.mode = "frontier"
    if args.metrics_port is not None:
        # fail-soft inside start_http_server: a failed bind (port
        # taken, privileged) must not kill the session before the
        # JSON line can be emitted
        import atexit

        from dmclock_tpu.obs import start_http_server
        http_srv = start_http_server(port=args.metrics_port)
        if http_srv is not None:
            print(f"# metrics: serving {http_srv.url}",
                  file=sys.stderr)
            atexit.register(http_srv.close)

    wm = args.device_metrics == "on"
    tele_on = args.telemetry == "on"
    slo_on = args.slo == "on"
    prov_on = args.provenance == "on"
    if args.trace_out:
        args.spans = True
    tracer = obsspans.SpanTracer() if args.spans else None
    watchdog = None
    from dmclock_tpu.obs import compile_plane as _cplane
    if tracer is not None:
        # compile records ride the same span stream as the launches
        # they delay (category "compile"; docs/OBSERVABILITY.md
        # capacity plane)
        _cplane.plane().set_tracer(tracer)
        # steady-state watchdog: warns live when the launch cadence
        # stalls, the dispatch share breaches its threshold, or a jit
        # cache entry retraces storm-fast (docs/OBSERVABILITY.md)
        from dmclock_tpu.obs import default_registry
        from dmclock_tpu.obs.watchdog import Watchdog
        watchdog = Watchdog(tracer, interval_s=2.0,
                            stall_after_s=60.0,
                            registry=default_registry(),
                            compile_plane=_cplane.plane()).start()
    from dmclock_tpu.robust.guarded import DegradationLadder
    ladder = DegradationLadder(enabled=not args.no_ladder,
                               threshold=1, tracer=tracer)

    def emit(out: dict) -> None:
        """THE json line: every exit path goes through here."""
        out["backend"] = backend
        # chaos sessions self-identify so the regression series stays
        # clean (scripts/bench_guard.py; docs/ROBUSTNESS.md)
        out["fault_plan"] = args.fault_plan
        # supervised/resumed sessions self-identify the same way: a
        # restart-bearing run's rates include recovery work, not the
        # engine alone
        if args.supervised:
            out["supervised"] = True
            out["restarts"] = restarts
        if ladder.steps_taken:
            out["degradation_ladder"] = ladder.describe()
        if watchdog is not None:
            watchdog.close()
            if watchdog.warnings:
                out["watchdog_warnings"] = watchdog.warnings[-8:]
        if tracer is not None and args.trace_out:
            # export on EVERY exit path (the emit contract): a failed
            # run's timeline is exactly when you want the trace
            try:
                from dmclock_tpu.obs import export_chrome_trace
                n_ev = export_chrome_trace(tracer, args.trace_out)
                print(f"# trace-out: {n_ev} spans -> "
                      f"{args.trace_out}", file=sys.stderr)
            except OSError as e:
                print(f"# trace-out failed: {e}", file=sys.stderr)
        print(json.dumps(out))

    if args.mode == "frontier":
        pick, rows = bench_frontier(
            target_latency_ms=args.target_latency)
        out = {"metric": "cfg4 throughput/latency frontier "
                         "(calendar engine; device-side round mean + "
                         "windowed completion-interval percentiles)",
               "rows": rows}
        if pick is not None:
            out["picked"] = pick
            out["metric"] += (f"; --target-latency "
                              f"{args.target_latency}ms pick: "
                              f"m={pick['m']} "
                              f"{pick['dps']/1e6:.1f}M dec/s at "
                              f"{pick['round_ms_mean']:.1f}ms rounds"
                              + ("" if pick["met_budget"] else
                                 " (budget NOT met; closest point)"))
        emit(out)
        try:
            _record_history({"frontier_" + str(r["m"]): r
                             for r in rows},
                            fault_plan=args.fault_plan,
                            supervised=args.supervised,
                            restarts=restarts)
        except OSError:
            pass
        return
    trace_ctx = (jax.profiler.trace(args.profile) if args.profile
                 else contextlib.nullcontext())

    def run_workloads() -> dict:
        results = {}
        loops = ("round", "stream") if args.engine_loop == "both" \
            else (args.engine_loop,)
        if args.mode in ("all", "serve"):
            serve_kw = dict(with_metrics=wm, tracer=tracer)
            impls = ("sort", "radix") if args.select_impl == "both" \
                else (args.select_impl,)
            for impl in impls:
                row, eff = _with_ladder(
                    ladder, {"select_impl": impl},
                    lambda select_impl: bench_serve_only(
                        select_impl=select_impl, **serve_kw))
                # key by the EFFECTIVE impl: a ladder step-down must
                # not masquerade as the requested fast path's history
                # series (setdefault: if radix degraded into sort and
                # sort already ran, the duplicate row is dropped)
                key = "serve" if eff["select_impl"] == "sort" \
                    else "serve_radix"
                results.setdefault(key, row)
        if args.mode in ("all", "cfg3"):
            # 10k clients, uniform QoS, Poisson arrivals; weight
            # regime.  Rounds are small (~130k decisions, ~7ms), so
            # the chains must be long for the differenced pairs to
            # clear host-clock jitter.
            cfg3_shape = dict(n=10_000, k=4096, m=32, rounds=60,
                              zipf=False, resv_rate=100.0,
                              dt_round_ns=100_000_000, ring=256,
                              depth0=128, rounds_lo=20)
            for loop in loops:
                key = "cfg3" if loop == "round" else "cfg3_stream"
                sh = dict(cfg3_shape)
                sh_pos = (sh.pop("n"), sh.pop("k"), sh.pop("m"),
                          sh.pop("rounds"))
                results[key], _ = _with_ladder(
                    ladder,
                    {"select_impl": "radix"
                     if args.select_impl == "radix" else "sort"},
                    lambda select_impl, loop=loop, sh=sh,
                    sh_pos=sh_pos: bench_sustained(
                        *sh_pos, **sh, with_metrics=wm,
                        select_impl=select_impl,
                        engine_loop=loop,
                        stream_chunk=args.stream_chunk,
                        telemetry=tele_on, slo=slo_on,
                        provenance=prov_on,
                        capacity_check=args.capacity == "on",
                        tracer=tracer, watchdog=watchdog))
        if args.mode in ("all", "churn"):
            # open-population churn scenario (docs/LIFECYCLE.md)
            key = f"churn_{args.churn_scenario}"
            results[key] = bench_churn(args.churn_scenario,
                                       slo=slo_on, tracer=tracer,
                                       total_ids=4096, epochs=64,
                                       k=256)
        if args.mode == "mesh":
            # the mesh serving plane's aggregate-throughput series
            # (the >=100M dec/s @ 1M clients target is this command).
            # --fault-plan "seed=..,p_dropout=.." (a parseable SPEC,
            # not just a label) samples a real FaultPlan and compiles
            # it INTO the chunks -- the chaos mesh session
            # (docs/ROBUSTNESS.md "Degraded-mode mesh")
            from dmclock_tpu.robust import faults as _faults
            mesh_fault_spec = _faults.parse_fault_spec(
                args.fault_plan)
            results["mesh"] = bench_mesh(
                args.clients, n_shards=args.n_shards,
                counter_sync_every=args.counter_sync_every,
                chunk=args.stream_chunk, with_metrics=wm,
                slo=slo_on, tracer=tracer,
                fault_spec=mesh_fault_spec)
            if mesh_fault_spec is not None:
                # the history/JSON tag becomes the sampled plan's
                # describe() summary (chaos sessions self-identify;
                # bench_guard keeps them out of clean medians)
                args.fault_plan = results["mesh"].get(
                    "fault_plan", args.fault_plan)
            if args.rebalance == "on":
                # the shard-rebalancing A/B rides the mesh session as
                # its own row; the mesh series above is untouched
                # (its identity carries rebalance="off"/P=static)
                results["mesh_rebalance"] = bench_mesh_rebalance(
                    n_shards=args.n_shards or 4, tracer=tracer)
        if args.mode == "controller":
            # the closed-loop controller A/B (docs/CONTROLLER.md):
            # exact supervised twins per churn scenario, differing
            # only in EpochJob(controller=...)
            results.update(bench_controller(
                sides=args.controller, tracer=tracer,
                total_ids=192, epochs=48))
        if args.mode == "rpc":
            # the RPC ingest front-end leg (docs/RPC.md): real
            # loopback sockets + N concurrent loadgen workers, then
            # the digest gate vs a self-generated replay of the
            # journaled admitted-counts trace
            results.update(bench_rpc(
                workers=args.rpc_workers,
                fault_spec=args.rpc_fault_spec, tracer=tracer,
                n=32, epochs=16, requests=64))
            if args.rpc_fault_spec:
                # chaos sessions self-identify in the history record
                # (bench_guard keeps them out of clean medians)
                args.fault_plan = "rpc:" \
                    + results["rpc"]["scenario"]
        if args.mode in ("all", "cfg4"):
            # 100k clients, Zipfian weights, reservation-constrained
            # (constraint share auto-calibrated to 0.50 -- a faster
            # engine needs a proportionally larger floor for the same
            # phase mix; round-5 equilibrium lands near 1200/s/client).
            # Calendar engine, m=3 batches x 64 serve-steps/client:
            # the frontier sweep showed decisions/round are capped by
            # the load generator (waves=64 ~ 5.8M arrivals/round), so
            # the smallest m whose per-client budget covers the
            # per-round arrival cap (192 >= 63) is strictly fastest
            # (m=12 commits the same decisions in 4x the passes).
            # --calendar-impl A/Bs the bucketed stop-key ladder
            # against minstop (separate bench_guard series; the JSON
            # line records decisions_per_pass for each).
            cals = ("minstop", "bucketed", "wheel") \
                if args.calendar_impl == "both" \
                else (args.calendar_impl,)
            for cal in cals:
                for loop in loops:
                    row, eff = _with_ladder(
                        ladder, {"calendar_impl": cal},
                        lambda calendar_impl, loop=loop:
                        bench_sustained(
                            100_000, 0, 3, 40, zipf=True,
                            resv_rate=1200.0, dt_round_ns=50_000_000,
                            waves=64, rounds_lo=12,
                            latency_rounds=100,
                            calendar_steps=64, target_resv_share=0.5,
                            reps=4, with_metrics=wm,
                            calendar_impl=calendar_impl,
                            ladder_levels=args.ladder_levels,
                            wheel_kernel=args.wheel_kernel,
                            engine_loop=loop,
                            stream_chunk=args.stream_chunk,
                            conformance_out=args.conformance_out,
                            telemetry=tele_on, slo=slo_on,
                            provenance=prov_on,
                            capacity_check=args.capacity == "on",
                            tracer=tracer, watchdog=watchdog))
                    # keyed by the EFFECTIVE impl: a ladder step-down
                    # mid-session must land the row in the series it
                    # actually measured (wheel -> bucketed -> minstop)
                    key = "cfg4" if eff["calendar_impl"] == "minstop" \
                        else f"cfg4_{eff['calendar_impl']}"
                    if loop == "stream":
                        key += "_stream"
                    results.setdefault(key, row)
        return results

    with trace_ctx:
        try:
            results = run_workloads()
        except Exception as e:
            # a failed run still leaves its record (with the trace,
            # if one was asked for), then fails the command
            emit({"metric": f"bench failed mid-run "
                            f"({type(e).__name__}); no usable rate",
                  "value": 0.0, "unit": "decisions/sec/chip",
                  "vs_baseline": 0.0,
                  "error": f"{type(e).__name__}: {e}"})
            raise
    c4 = results.get("cfg4") or results.get("cfg4_bucketed") \
        or results.get("cfg4_wheel") \
        or results.get("cfg4_stream") \
        or results.get("cfg4_bucketed_stream") \
        or results.get("cfg4_wheel_stream")
    primary = c4 or results.get("cfg3") or results.get("cfg3_stream") \
        or results.get("serve") or next(iter(results.values()))
    parts = []
    for key in ("serve", "serve_radix"):
        if key in results:
            label = "serve-only" if key == "serve" \
                else "serve-only[radix]"
            parts.append(f"{label} {results[key]['dps']/1e6:.1f}M "
                         f"(fill {results[key]['fill']:.2f})")
    if "cfg3" in results:
        r = results["cfg3"]
        parts.append(f"cfg3 10k-client Poisson sustained "
                     f"{r['dps']/1e6:.1f}M (fill {r['fill']:.2f}, "
                     f"depth {r['mean_depth']:.0f})")
    if "cfg3_stream" in results:
        r = results["cfg3_stream"]
        parts.append(f"cfg3[stream] {r['dps']/1e6:.1f}M "
                     f"({r['decisions_per_launch']:.0f} dec/launch, "
                     f"chunk {r.get('stream_chunk', 0)})")
    for key, label in (("cfg4", "cfg4"),
                       ("cfg4_bucketed", "cfg4[bucketed]"),
                       ("cfg4_wheel", "cfg4[wheel]"),
                       ("cfg4_stream", "cfg4[stream]"),
                       ("cfg4_bucketed_stream",
                        "cfg4[bucketed,stream]"),
                       ("cfg4_wheel_stream",
                        "cfg4[wheel,stream]")):
        r4 = results.get(key)
        if not r4:
            continue
        parts.append(
            f"{label} 100k-client Zipf resv-constrained "
            f"{r4['dps']/1e6:.1f}M (resv phase "
            f"{r4['resv_phase_frac']:.2f}; "
            f"{r4.get('decisions_per_pass', 0):.0f} dec/pass; "
            f"round mean "
            f"{r4.get('round_ms_mean', 0):.0f}ms device-side, "
            f"measured-interval p50 "
            f"{r4.get('round_ms_p50', 0):.0f}ms p99 "
            f"{r4.get('round_ms_p99', 0):.0f}ms host-inclusive "
            f"upper bounds)")
    if results.get("mesh", {}).get("capacity_skipped"):
        r = results["mesh"]
        parts.append(
            f"mesh SKIPPED by the capacity gate "
            f"({r['clients_per_shard']} clients/shard > planned "
            f"{r.get('max_clients_per_shard')} for the detected "
            "budget)")
    elif "mesh" in results:
        r = results["mesh"]
        planned = r.get("shards_planned")
        parts.append(
            f"mesh {r['n_shards']} shards x "
            f"{r['clients_per_shard']} clients "
            f"{r['dps']/1e6:.1f}M aggregate "
            f"({r['dps_per_shard_mean']/1e6:.2f}M/shard, "
            f"sync every {r['counter_sync_every']} epochs, "
            f"{r['counter_bytes_per_epoch']:.0f} B/epoch counter "
            f"exchange"
            + (", collective-free non-sync epochs"
               if r.get("collective_skipping") else "")
            + (f", {planned} shards planned from the HBM ledger"
               if planned is not None else "") + ")")
    if "mesh_rebalance" in results:
        r = results["mesh_rebalance"]
        parts.append(
            f"rebalance[{r['scenario']}] skew "
            f"{r['shard_skew_before']:.2f} -> "
            f"{r['shard_skew_after']:.2f} over {r['n_shards']} "
            f"shards ({r['migrations']} migrations; "
            f"{r['dps_on']/1e6:.2f}M on vs {r['dps_off']/1e6:.2f}M "
            f"off, {r['recovered_dps']/1e6:+.2f}M recovered)")
    for key in sorted(results):
        if not key.startswith("churn_"):
            continue
        r = results[key]
        b = r.get("boost")
        put = (f"; live PUT weight "
               f"x{b['weight_after']/max(b['weight_before'], 1e-9):.0f}"
               f" -> delivered share x{b['share_gain']:.1f}") \
            if b else ""
        parts.append(
            f"churn[{r['scenario']}] {r['dps']/1e6:.2f}M over an "
            f"open population (peak {r['peak_clients']} clients, "
            f"{r['evictions']} evictions, {r['slot_recycles']} "
            f"recycles, {r['compactions']} compactions{put})")
    for key in sorted(results):
        if not key.startswith("controller_"):
            continue
        r = results[key]
        if "recovered_dps" in r:
            parts.append(
                f"controller[{r['scenario']}] "
                f"{r['dps_on']/1e6:.2f}M on vs "
                f"{r['dps_off']/1e6:.2f}M off "
                f"({r['recovered_dps']/1e6:+.2f}M recovered; burn "
                f"{r['burn_epochs_on']} vs {r['burn_epochs_off']} "
                f"epochs; {r.get('controller_decisions', 0)} "
                f"actuations)")
        else:
            side = "on" if "dps_on" in r else "off"
            parts.append(
                f"controller[{r['scenario']},{side}] "
                f"{r['dps']/1e6:.2f}M (burn "
                f"{r.get('burn_epochs_' + side, 0)} epochs"
                + (f"; {r.get('controller_decisions', 0)} "
                   f"actuations)" if side == "on" else ")"))
    if "rpc" in results:
        r = results["rpc"]
        parts.append(
            f"rpc[{r['scenario']}] {r['workers']} workers over real "
            f"loopback sockets ({r['admitted_ops']} ops admitted, "
            f"digest {'MATCH' if r['digest_match'] else 'MISMATCH'} "
            f"vs journaled-trace replay"
            + (", chaos accounting "
               + ("EXACT" if r["chaos_exact"] else "INEXACT")
               if r["scenario"] != "none" else "")
            + f"; admit->commit p99 {r['lat_p99_ms']:.0f}ms)")

    # device histogram blocks feed the live scrape registry per
    # workload (proper Prometheus _bucket/_sum/_count families), then
    # leave the result rows -- the JSON line carries the readable
    # "telemetry" digest instead of the raw block twice
    for wl, row in results.items():
        hb = row.pop("_hist_block", None)
        if hb is not None:
            from dmclock_tpu.obs import default_registry
            from dmclock_tpu.obs import histograms as obshist
            obshist.publish_hists(default_registry(),
                                  np.asarray(hb, dtype=np.int64),
                                  labels={"workload": wl})
        if "provenance" in row:
            # per-workload provenance verdicts as labelled gauges on
            # the same scrape endpoint (dmclock_provenance_* /
            # dmclock_starvation_* family names)
            from dmclock_tpu.obs import default_registry
            reg = default_registry()
            pd = row["provenance"]
            for key in ("margin_p50_ns", "margin_p99_ns",
                        "limit_gate_share", "eligible_depth_mean",
                        "eligible_depth_max"):
                reg.gauge(f"dmclock_provenance_{key}",
                          "per-workload decision provenance scalar "
                          "(docs/OBSERVABILITY.md Provenance plane)",
                          labels={"workload": wl}) \
                    .set(float(pd[key]))
            reg.gauge("dmclock_starvation_max_ns",
                      "per-workload starvation watermark "
                      "(provenance plane)",
                      labels={"workload": wl}) \
                .set(float(pd["starvation_max_ns"]))
        if "slo" in row:
            # per-workload SLO verdicts as labelled gauges on the
            # same scrape endpoint (dmclock_slo_* family names)
            from dmclock_tpu.obs import default_registry
            reg = default_registry()
            for key, name in (
                    ("violations_total",
                     "dmclock_slo_violations_total"),
                    ("worst_window_share_err",
                     "dmclock_slo_worst_window_share_err"),
                    ("window_tardiness_p99_ns",
                     "dmclock_slo_window_tardiness_p99_ns"),
                    ("windows_closed",
                     "dmclock_slo_windows_closed_total")):
                reg.gauge(name, "per-workload SLO plane verdict "
                          "(docs/OBSERVABILITY.md SLO plane)",
                          labels={"workload": wl}) \
                    .set(float(row["slo"].get(key, 0)))

    try:
        _record_history(results, fault_plan=args.fault_plan,
                        supervised=args.supervised, restarts=restarts,
                        ladder_steps=ladder.describe(),
                        controller=args.controller
                        if args.mode == "controller" else "off")
    except OSError as e:      # telemetry must never eat the results
        print(f"# history record failed: {e}", file=sys.stderr)
    final = {
        "metric": "dmclock sustained scheduling decisions/sec, "
                  "ARRIVALS INCLUDED (Poisson superwave ingest on "
                  "device each round; cfg4 on the sortless calendar "
                  "engine, serve/cfg3 on the sorted prefix engine, "
                  "both bit-exact vs the serial engine; counts read "
                  "back untimed) -- " + "; ".join(parts),
        "value": round(primary["dps"], 1),
        "unit": "decisions/sec/chip",
        "vs_baseline": round(primary["dps"] / 10_000_000, 4),
    }
    c4conf = c4.get("conformance") if c4 else None
    if c4conf:
        final["conformance"] = c4conf
    # the churn scenario's full block (lifecycle counters, the
    # per-client before/after shares, the live-PUT effect) rides the
    # JSON line -- the ISSUE-9 visible-effect acceptance output
    churn_rows = {wl: {k: v for k, v in row.items()
                       if k != "_hist_block"}
                  for wl, row in results.items()
                  if wl.startswith("churn_")}
    if churn_rows:
        final["churn"] = churn_rows
    # the controller A/B's full rows (recovered dec/s, burn-episode
    # durations, actuation trajectory) ride the JSON line -- the
    # PR-18 acceptance output; the scalar fields land in the history
    # record through the same _record_history scalar filter as every
    # other workload
    ctl_rows = {wl: {k: v for k, v in row.items()
                     if k != "_hist_block"}
                for wl, row in results.items()
                if wl.startswith("controller_")}
    if ctl_rows:
        final["controller"] = ctl_rows
    # the mesh serving plane's full row (aggregate + per-shard dec/s,
    # counter-exchange accounting, shard plan) rides the JSON line --
    # the MULTICHIP v2 record reads it straight off stdout
    if "mesh" in results:
        final["mesh"] = {k: v for k, v in results["mesh"].items()
                         if k != "_hist_block"}
    # the shard-rebalancing A/B row (--rebalance on): the MULTICHIP
    # v3 record's rebalance block reads it straight off stdout
    if "mesh_rebalance" in results:
        final["mesh_rebalance"] = dict(results["mesh_rebalance"])
    if wm and "device_metrics" in primary:
        final["device_metrics"] = primary["device_metrics"]
    # per-epoch XLA attribution + what bounded each sustained run ride
    # the same JSON line (and the obs registry, for live scrapes)
    cost_all = {wl: row["cost_analysis"] for wl, row in results.items()
                if isinstance(row.get("cost_analysis"), dict)}
    if cost_all:
        final["cost_analysis"] = cost_all
        for wl, ca in cost_all.items():
            _feed_cost_registry(wl, ca)
    bounded = {wl: row["bounded_by"] for wl, row in results.items()
               if "bounded_by" in row}
    if bounded:
        final["bounded_by"] = bounded
    # real tardiness percentiles from the device telemetry plane (the
    # sims' host-computed table, replaced by device truth at bench
    # scale); log2-quantized upper bounds, never under-reported
    # the per-launch dispatch-tax decomposition per workload (span
    # tracer; the before/after currency for the streaming-loop PR)
    span_rows = {wl: row["spans"] for wl, row in results.items()
                 if "spans" in row}
    if span_rows:
        final["spans"] = span_rows
    slo_rows = {wl: row["slo"] for wl, row in results.items()
                if "slo" in row}
    if slo_rows:
        final["slo"] = slo_rows
    prov_rows = {wl: row["provenance"] for wl, row in results.items()
                 if "provenance" in row}
    if prov_rows:
        final["provenance"] = prov_rows
    tard = {wl: {"p50": row["tardiness_p50_ns"],
                 "p90": row["tardiness_p90_ns"],
                 "p99": row["tardiness_p99_ns"],
                 "mean": row["tardiness_mean_ns"],
                 "max": row["tardiness_max_ns"]}
            for wl, row in results.items()
            if "tardiness_p99_ns" in row}
    if tard:
        final["tardiness_ns"] = tard
    # capacity plane session record (docs/OBSERVABILITY.md "Capacity
    # plane"): compile/retrace totals over every instrumented jit
    # cache, per-workload projections + roofline verdicts, and the
    # detected device budget -- the full capacity record the next
    # silicon session captures with zero extra flags
    try:
        from dmclock_tpu.obs import (capacity as obscap,
                                     default_registry,
                                     publish_compile_metrics)
        from dmclock_tpu.obs.capacity import publish_capacity_metrics
        cp = _cplane.plane()
        final["compile"] = cp.totals()
        publish_compile_metrics(default_registry())
        budget = obscap.device_hbm_budget()
        cap_block = {}
        if budget is not None:
            cap_block["budget_bytes"] = int(budget)
        for wl, row in results.items():
            if "projected_hbm_bytes" in row:
                cap_block.setdefault("projected_hbm_bytes", {})[wl] = \
                    row["projected_hbm_bytes"]
                publish_capacity_metrics(
                    default_registry(),
                    projected_bytes=row["projected_hbm_bytes"],
                    budget_bytes=budget, workload=wl)
            if "bound_class" in row:
                cap_block.setdefault("bound_class", {})[wl] = \
                    row["bound_class"]
            if "compile_ms_total" in row:
                cap_block.setdefault("compile_ms_total", {})[wl] = \
                    row["compile_ms_total"]
                cap_block.setdefault("retraces", {})[wl] = \
                    row.get("retraces", 0)
        if cap_block:
            final["capacity"] = cap_block
    except Exception as e:   # the capacity record must never eat the
        final["capacity_error"] = f"{type(e).__name__}: {e}"  # line
    emit(final)


def _record_history(results: dict, fault_plan: str = "none",
                    supervised: bool = False, restarts: int = 0,
                    ladder_steps=None,
                    controller: str = "off") -> None:
    """Append this session's rates to benchmark/history/ for the
    drift-aware regression guard (scripts/bench_guard.py).
    ``fault_plan`` != "none" marks a chaos session: recorded for the
    trajectory, excluded from the clean-run medians.  ``supervised``
    / ``restarts`` mark a session run under robust.supervisor: a
    restart-bearing run's wall time includes recovery (resume +
    replay), so the guard excludes it the same way.  ``controller``
    != "off" marks a closed-loop controller A/B session
    (docs/CONTROLLER.md): the on-twin's wall time includes actuation
    recompiles, so the guard keeps controller-actuated sessions out
    of the clean medians while the trajectory stays recorded."""
    from pathlib import Path

    if not results:
        return
    platform = jax.devices()[0].platform
    hist = Path(__file__).resolve().parent / "benchmark" / "history"
    hist.mkdir(parents=True, exist_ok=True)
    rec = {
        "platform": platform,
        "device": str(jax.devices()[0]),
        "fault_plan": fault_plan,
        # scalars AND tags: select_impl / bounded_by are strings the
        # guard needs (separate per-impl series; stall attribution)
        "workloads": {
            wl: {k: v for k, v in row.items()
                 if isinstance(v, (int, float, str, bool))}
            for wl, row in results.items()},
    }
    if supervised:
        rec["supervised"] = True
        rec["restarts"] = int(restarts)
    if controller != "off":
        rec["controller"] = controller
    if ladder_steps:
        rec["degradation_ladder"] = ladder_steps
    out = hist / f"bench_{int(time.time())}.json"
    out.write_text(json.dumps(rec, indent=1))
    print(f"# recorded {out.relative_to(hist.parent.parent)}",
          file=__import__('sys').stderr)


if __name__ == "__main__":
    main()
