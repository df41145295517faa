#!/usr/bin/env python
"""Benchmark sweeps (the reference benchmark/ pipeline analog,
``benchmark/data_gen.sh:28-38`` + ``plot_gen.sh``):

1. K-sweep: native ``dmc_sim_native --k-way K`` for K=2..10 over the
   acceptance config, harvesting the mean ns-per-call numbers the
   reference pipeline greps (``simulate.h:306-349``).  The reference's
   rule of thumb ("<= 6 elements: K small; otherwise K=3",
   benchmark/README.md:17-19) is what this reproduces with runtime K.
2. TPU k/m sweep: ``scan_prefix_epoch`` decisions/sec at 100k clients
   across batch size k and epoch length m (the analog of the
   K_WAY_HEAP study for the batch engine: k amortizes the selection
   sort; prefix-commit makes k past the re-entry distance a fill
   degradation instead of a cliff).

Writes benchmark/RESULTS.md.  Usage:
    python benchmark/run_sweeps.py [--skip-native] [--skip-tpu]
        [--repeat N]
"""

from __future__ import annotations

import argparse
import functools
import re
import statistics
import subprocess
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BUILD = REPO / "native" / "build"
RESULTS = Path(__file__).resolve().parent / "RESULTS.md"


def build_native() -> Path:
    exe = BUILD / "dmc_sim_native"
    subprocess.run(["cmake", "-S", str(REPO / "native"), "-B",
                    str(BUILD)], check=True, capture_output=True)
    subprocess.run(["cmake", "--build", str(BUILD), "-j", "--target",
                    "dmc_sim_native"], check=True, capture_output=True)
    return exe


def native_k_sweep(repeat: int):
    exe = build_native()
    # the reference sweep's workload (benchmark/configs/
    # dmc_sim_100_100.conf): 100 servers x 100 clients, 1M ops
    conf = REPO / "configs" / "dmc_sim_100_100.conf"
    rows = []
    for k in range(2, 11):
        add_ns, wall = [], []
        for r in range(repeat):
            t0 = time.perf_counter()
            out = subprocess.run(
                [str(exe), "-c", str(conf), "--k-way", str(k),
                 "--seed", str(12345 + r)],
                check=True, capture_output=True, text=True,
                timeout=600).stdout
            wall.append(time.perf_counter() - t0)
            m = re.search(r"average add_request:\s+(\d+) ns", out)
            add_ns.append(int(m.group(1)))
        rows.append((k, statistics.mean(add_ns),
                     statistics.mean(wall)))
        print(f"K={k}: add_request {rows[-1][1]:.0f} ns "
              f"(wall {rows[-1][2]:.2f}s)")
    return rows


def _timed_prefix_epochs(make_state, now_ns, epochs_hi, k, m,
                         epochs_lo=None, reps=3):
    """Differenced-chain timing on the prefix-commit engine (matches
    bench.py's protocol): a short chain of ``epochs_hi // 4`` epochs
    and a long one of ``epochs_hi``, each chained async with ONE digest
    sync; ``(D_hi - D_lo) / (T_hi - T_lo)`` cancels the fixed per-chain
    dispatch/sync overhead exactly.  (Round 3 subtracted one measured
    scalar latency instead, which left chain-length-dependent overhead
    in the result -- the 50M-vs-103M protocol discrepancy of the
    round-3 review.)

    Backlog bounds keep the chains short (tens to hundreds of ms of
    device work), so one differenced pair still carries host jitter
    of the same order -- single-shot rates at the big-k shapes spread
    41-71M run to run.  The reported rate is the MEDIAN over ``reps``
    fresh-state repetitions.

    BOTH chains must be device-bound: wall = max(device, sync RTT), so
    a lo chain under the ~100ms RTT floor truncates the difference's
    denominator and the rate explodes.  The lo chain is sized to hold
    >= 2^22 decisions (~150ms+ of device work at the plateau rates)
    and reps whose lo wall still sits at the floor are discarded.
    Returns (decisions/sec, fill)."""
    import jax
    import jax.numpy as jnp
    from dmclock_tpu.engine.fastpath import scan_prefix_epoch
    from profile_util import scalar_latency, state_digest

    run = jax.jit(functools.partial(
        scan_prefix_epoch, m=m, k=k, anticipation_ns=0),
        donate_argnums=(0,))
    if epochs_lo is None:
        # >= 3*2^21 decisions ~= 160ms+ of device work at the plateau
        # rates (matches bench.py's serve-only lo-chain sizing)
        epochs_lo = max(1, epochs_hi // 4,
                        -((3 << 21) // -(m * k)))      # ceil div
    epochs_hi = max(epochs_hi, epochs_lo + 1)
    lat = scalar_latency()

    def chain(state, n):
        t0 = time.perf_counter()
        counts, guards = [], []
        for _ in range(n):
            ep = run(state, jnp.int64(now_ns))
            state = ep.state
            counts.append(ep.count)
            guards.append(ep.guards_ok)
        jax.device_get(state_digest(state))
        wall = time.perf_counter() - t0
        assert all(bool(jax.device_get(g).all()) for g in guards), \
            "rebase guards tripped -- counts are not trustworthy"
        total = int(sum(int(jax.device_get(c).sum()) for c in counts))
        return state, total, wall

    rates, d_all, pot_all = [], 0, 0
    for rep in range(max(reps, 1)):
        state = make_state()
        # one retry covers a transient runtime/transport error (the
        # cache makes the second attempt cheap); a trace-time
        # programming error must fail fast.
        # Retry ONLY if the donated input buffer survived: a post-
        # dispatch failure consumes it, and retrying would mask the
        # original error with a deleted-buffer error.
        for attempt in (0, 1):
            try:
                ep = run(state, jnp.int64(now_ns))   # warm/compile
                break
            except jax.errors.JaxRuntimeError:
                if attempt or any(
                        getattr(x, "is_deleted", lambda: False)()
                        for x in jax.tree_util.tree_leaves(state)):
                    raise
                time.sleep(2)
                state = make_state()
        jax.device_get(state_digest(ep.state))
        state = ep.state
        if rep == 0:
            # backlog sufficiency with the 1.5x heavy-class margin
            # (bench.py's rule: weights 1..4 serve the heaviest class
            # ~1.6x the mean; chains sized to the MEAN backlog drain
            # heavy clients mid-chain and deflate the rate)
            backlog = int(jax.device_get(
                state.depth.astype(jnp.int64).sum()))
            assert (epochs_lo + epochs_hi) * m * k * 3 // 2 <= backlog, \
                f"backlog {backlog} cannot feed chains at k={k} " \
                f"m={m} with heavy-class margin"
        state, d_lo, t_lo = chain(state, epochs_lo)
        state, d_hi, t_hi = chain(state, epochs_hi)
        d_all += d_lo + d_hi
        pot_all += (epochs_lo + epochs_hi) * m * k
        if t_hi <= t_lo or t_lo < 1.2 * lat:
            continue    # jitter-inverted or RTT-floor-bound lo chain
        rates.append((d_hi - d_lo) / (t_hi - t_lo))
    assert rates, \
        "no valid pair: chains too short for the host RTT floor"
    import statistics
    return statistics.median(rates), d_all / pot_all


def _timed_transient_chain(state, now_ns, epochs, k, m):
    """Single measured chain for NON-stationary shapes (a transition
    is consumed once, so chain differencing cannot apply): compile on
    a disposable copy of the state, then time one chain from the
    intact original, subtracting one measured scalar round-trip.
    Transient rates carry the host noise the differenced protocol
    cancels -- treat them as approximate."""
    import jax
    import jax.numpy as jnp
    from dmclock_tpu.engine.fastpath import scan_prefix_epoch
    from profile_util import scalar_latency, state_digest

    run = jax.jit(functools.partial(
        scan_prefix_epoch, m=m, k=k, anticipation_ns=0),
        donate_argnums=(0,))
    warm = run(jax.tree.map(jnp.copy, state), jnp.int64(now_ns))
    jax.device_get(state_digest(warm.state))
    del warm
    lat = scalar_latency()
    t0 = time.perf_counter()
    counts, guards = [], []
    for _ in range(epochs):
        ep = run(state, jnp.int64(now_ns))
        state = ep.state
        counts.append(ep.count)
        guards.append(ep.guards_ok)
    jax.device_get(state_digest(state))
    t = time.perf_counter() - t0 - lat
    assert all(bool(jax.device_get(g).all()) for g in guards), \
        "rebase guards tripped -- counts are not trustworthy"
    total = int(sum(int(jax.device_get(c).sum()) for c in counts))
    assert t > 0, f"timing underflow: {t:.4f}s"
    return total / t, total / (epochs * m * k)


def tpu_km_sweep():
    import sys
    sys.path.insert(0, str(REPO))
    from __graft_entry__ import _preloaded_state

    n, depth = 100_000, 256
    rows = []
    # focused grid: the m axis at the argmax k (dispatch-amortization
    # story) plus the k axis at the argmax m; 3 fresh-state reps per
    # point (median) keep the short-chain shapes jitter-stable.  The
    # largest shapes need deeper rings for the heavy-class backlog
    # margin (ring width itself costs; keep the smallest that fits).
    grid = [(65536, m, 320) for m in (8, 21, 32)] + \
        [(65536, 64, 384),
         (16384, 64, 256), (32768, 64, 256), (49152, 64, 384),
         (98304, 64, 384)]
    for k, m, d in grid:
        hi = max(2, (1 << 23) // (m * k))

        def mk(depth=d):
            return _preloaded_state(n, depth, ring=depth)

        dps, fill = _timed_prefix_epochs(mk, 0, hi, k, m)
        rows.append((k, m, dps, fill))
        print(f"k={k} m={m}: {dps/1e6:.2f} M dec/s "
              f"(fill {fill:.3f})", flush=True)
    return rows


def tpu_regime_sweep():
    """Decisions/sec by REGIME on the prefix-commit engine: pure
    reservation backlog (constraint phase every decision), a
    reservation->weight transition mid-run (the prefix stops exactly at
    the flip and the next batch switches regime -- formerly the serial-
    recovery cliff), the weight steady state, and the exact serial
    engine as the floor."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import sys
    sys.path.insert(0, str(REPO))
    from __graft_entry__ import _preloaded_state
    from dmclock_tpu.engine import kernels
    from profile_util import scalar_latency, state_digest

    n, depth, k, m = 100_000, 256, 49152, 21
    rows = []

    def resv_state():
        st = _preloaded_state(n, depth, ring=depth)
        # stagger reservation phases over the serve period (2*rinv)
        c = np.arange(n)
        phase = ((c * 2654435761) & 0xFFFFF) / float(1 << 20)
        rinv = np.asarray(st.resv_inv)
        jit = (phase * 2.0 * rinv).astype(np.int64)
        return st._replace(head_resv=jnp.asarray(rinv + jit))

    # pure reservation regime: now far beyond every reservation tag
    dps, fill = _timed_prefix_epochs(resv_state, 10**15, 8, k, m)
    rows.append(("reservation backlog", dps, fill))
    print(f"reservation: {dps/1e6:.2f} M dec/s fill {fill:.3f}")

    # transition: only a few batches of reservation serves are
    # eligible, then the regime flips to weight mid-epoch.  The flip is
    # consumed once, so this row uses the single-chain transient
    # protocol (approximate), not chain differencing.
    st = resv_state()
    now = int(np.asarray(st.head_resv).min()) + 2 * 10**7
    dps, fill = _timed_transient_chain(st, now, 8, k, m)
    rows.append(("resv->weight transition (transient)", dps, fill))
    print(f"transition: {dps/1e6:.2f} M dec/s fill {fill:.3f}")

    # weight regime baseline at the same epoch budget
    dps, fill = _timed_prefix_epochs(
        lambda: _preloaded_state(n, depth, ring=depth), 0, 8, k, m)
    rows.append(("weight steady state", dps, fill))
    print(f"weight: {dps/1e6:.2f} M dec/s fill {fill:.3f}")

    # exact serial engine floor (single-chain, lat-corrected: the
    # serial scan is minutes-per-epoch slow, so chain differencing is
    # unnecessary -- overhead is < 1% here)
    lat = scalar_latency()
    state = _preloaded_state(n, depth, ring=depth)
    serial = jax.jit(lambda s, t: kernels.engine_run(
        s, t, 4096, allow_limit_break=False, anticipation_ns=0,
        advance_now=False))
    state, _, decs = serial(state, jnp.int64(0))
    jax.device_get(state_digest(state))
    t0 = time.perf_counter()
    state, _, decs = serial(state, jnp.int64(0))
    jax.device_get(state_digest(state))
    t = time.perf_counter() - t0 - lat
    rows.append(("exact serial engine", 4096 / t, 1.0))
    print(f"serial exact: {4096/t/1e3:.1f} k dec/s")
    return rows


def tpu_sustained_sweep():
    """BASELINE configs #3/#4: the sustained closed loop (Poisson
    superwave ingest + prefix epochs) as measured by bench.py."""
    import sys
    sys.path.insert(0, str(REPO))
    from bench import CFG4_RESV_RATE, bench_sustained

    rows = []
    r3 = bench_sustained(10_000, 4096, 32, 60, zipf=False,
                         resv_rate=100.0, dt_round_ns=100_000_000,
                         ring=256, depth0=128, rounds_lo=20)
    rows.append(("cfg3: 10k clients, uniform QoS, Poisson", r3))
    print(f"cfg3: {r3['dps']/1e6:.2f} M dec/s")
    r4 = bench_sustained(100_000, 49152, 21, 24, zipf=True,
                         resv_rate=CFG4_RESV_RATE,
                         dt_round_ns=50_000_000, rounds_lo=8)
    rows.append(("cfg4: 100k clients, Zipf weights, resv-constrained",
                 r4))
    print(f"cfg4: {r4['dps']/1e6:.2f} M dec/s")
    return rows


def cfg4_calibration_sweep():
    """The cfg4 reservation-rate calibration study: constraint-phase
    share and throughput vs reservation rate, for three population
    designs.  Mixed-QoS clients pin the share high at any realistic
    rate (weight serves' reservation-debt reduction re-arms the
    constraint phase, reference reduce_reservation_tags :1077-1111);
    cohort alignment and split populations were the candidate
    mitigations -- neither beats the simple mixed design at the target
    share, so cfg4 ships mixed with CFG4_RESV_RATE."""
    import sys
    sys.path.insert(0, str(REPO))
    from bench import bench_sustained

    rows = []
    cases = [
        ("mixed staggered", {}, (25.0, 50.0, 100.0, 200.0)),
        ("mixed aligned", {"resv_aligned": True}, (100.0, 200.0)),
        ("split 50/50", {"split_resv": 0.5}, (60.0, 90.0, 140.0)),
    ]
    for name, kw, rates in cases:
        for r in rates:
            out = bench_sustained(100_000, 49152, 21, 16, zipf=True,
                                  resv_rate=r, dt_round_ns=50_000_000,
                                  rounds_lo=8, **kw)
            rows.append((name, r, out))
            print(f"{name} r={r}: resv_phase="
                  f"{out['resv_phase_frac']:.3f} "
                  f"fill={out['fill']:.3f} "
                  f"dps={out['dps']/1e6:.1f}M", flush=True)
    return rows


def device_sim_headline():
    """Closed-loop ops/sec of the device-resident simulator at 100k
    clients -- the reference's system test (sim/src/simulate.h:159-178)
    run as ONE compiled program per launch: load generation, delta/rho
    piggybacking, dmClock scheduling, service, completion feedback all
    on device.  Prefix serve mode (q=4096 per slice), random server
    selection, 2-thread servers."""
    import dataclasses
    import functools
    import sys
    sys.path.insert(0, str(REPO))
    import jax
    import numpy as np
    from dmclock_tpu.sim import device_sim as DS
    from dmclock_tpu.sim.config import (ClientGroup, ServerGroup,
                                        SimConfig)

    n = 100_000
    groups = [
        ClientGroup(client_count=n // 2, client_total_ops=10**9,
                    client_iops_goal=80.0, client_outstanding_ops=32,
                    client_reservation=2.0, client_limit=0.0,
                    client_weight=1.0, client_server_select_range=8),
        ClientGroup(client_count=n // 2, client_total_ops=10**9,
                    client_iops_goal=80.0, client_outstanding_ops=32,
                    client_reservation=2.0, client_limit=0.0,
                    client_weight=3.0, client_server_select_range=8),
    ]
    cfg = SimConfig(client_groups=2, server_groups=1,
                    server_random_selection=True,
                    server_soft_limit=False, cli_group=groups,
                    srv_group=[ServerGroup(server_count=8,
                                           server_iops=500_000.0,
                                           server_threads=2)])
    sim, _ = DS.init_device_sim(cfg, ring_capacity=64)
    # rebuild the spec at the throughput slice size through _make_spec
    # so max_sends is re-derived for the longer slice (a stale
    # max_sends would silently clamp offered load below the goal --
    # the misreporting _make_spec's assert exists to refuse)
    spec = DS._make_spec(cfg, q_per_slice=4096)
    assert spec.q_per_slice >= 256 and not spec.force_scan
    mesh = DS.make_mesh(1)
    sim = DS.shard_device_sim(sim, mesh)
    # slices=2 + per-launch syncs: longer launches of this program
    # (vmap x while_loop x shard_map over 8 servers) faulted the
    # remote TPU worker of rounds 1-5; 2-slice launches ran 14+
    # consecutive times without incident.  Donation keeps one ~1GB state resident.
    slices = 2
    step = jax.jit(functools.partial(DS.device_sim_step, spec=spec,
                                     mesh=mesh, slices=slices),
                   donate_argnums=(0,))

    def served(s):
        return int(np.asarray(s.served_resv).sum()
                   + np.asarray(s.served_prop).sum())

    def chain(launches, s):
        # served_resv/served_prop are CUMULATIVE counters: take the
        # per-chain delta so the differenced rate's numerator and
        # denominator cover the same launches.  Launches are sync'd
        # INDIVIDUALLY: queueing several multi-second device_sim
        # launches asynchronously crashed the remote TPU worker of
        # rounds 1-5 ("kernel fault").  Differencing cancels only the
        # fixed per-CHAIN offset; each launch's sync round-trip stays
        # in the denominator, so the reported wall rate is a
        # host-inclusive, conservative figure.
        before = served(s)          # syncs the previous chain, untimed
        t0 = time.perf_counter()
        for _ in range(launches):
            s = step(s)
            jax.block_until_ready(s.served_resv)
        n_served = served(s) - before
        return s, n_served, time.perf_counter() - t0

    sim, _, _ = chain(1, sim)                      # warm/compile
    sim, d_lo, t_lo = chain(4, sim)
    sim, d_hi, t_hi = chain(10, sim)
    dps = (d_hi - d_lo) / (t_hi - t_lo)
    virt_s = int(np.asarray(sim.t)) / 1e9
    per_client = (np.asarray(sim.served_resv)
                  + np.asarray(sim.served_prop)).sum(axis=0)
    g2 = per_client[n // 2:].sum() / max(per_client[:n // 2].sum(), 1)
    row = {"ops_per_sec": dps, "total_ops": served(sim),
           "virtual_s": virt_s, "weight_ratio_3_1": float(g2)}
    print(f"device_sim closed loop: {dps/1e6:.2f} M ops/s wall "
          f"(weight 3:1 ratio {g2:.2f}, {d_hi} ops, "
          f"{virt_s:.1f}s virtual)")
    return row


def tpu_calendar_sweep():
    """Round-5 calendar engine rows: serve-only drain throughput by
    (m, steps) over the 100k-client weight steady state (single-chain,
    latency-corrected; chains sized to consume well under the 32M
    backlog so per-epoch commits stay representative).  The calendar
    batch has no [k] sort cap: per-batch commits are bounded by the
    per-client step budget x the population (~500k at steps=8 on
    weights 1..4) instead of the sorted engine's ~62k."""
    import functools
    import sys
    import time

    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(REPO))
    from __graft_entry__ import _preloaded_state
    from dmclock_tpu.engine.fastpath import scan_calendar_epoch
    from profile_util import scalar_latency, state_digest

    lat = scalar_latency()
    rows = []
    for m, steps, epochs in ((4, 8, 10), (8, 8, 5), (8, 16, 4)):
        run = jax.jit(functools.partial(
            scan_calendar_epoch, m=m, steps=steps, anticipation_ns=0),
            donate_argnums=(0,))
        st = _preloaded_state(100_000, 320, ring=320)
        ep = run(st, jnp.int64(0))
        jax.device_get(state_digest(ep.state))        # warm
        st = _preloaded_state(100_000, 320, ring=320)
        t0 = time.perf_counter()
        counts = []
        for _ in range(epochs):
            ep = run(st, jnp.int64(0))
            st = ep.state
            counts.append(ep.count)
        jax.device_get(state_digest(st))
        wall = time.perf_counter() - t0 - lat
        total = sum(int(jax.device_get(c).sum()) for c in counts)
        rows.append((m, steps, total / wall, total))
        print(f"calendar m={m} steps={steps}: {total/wall/1e6:.1f} "
              f"M dec/s ({total} decisions, {wall:.2f}s)")
    return rows


def tpu_allow_regime_row():
    """AtLimit::Allow on the fast paths (round-4 review: the Allow
    regime ran at 0.01M on the serial scan).  A limited population
    (weights > 0, tight limits, now past every limit) serves purely
    via limit-break: measured on the flat sorted batch and the
    calendar batch."""
    import functools
    import sys
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(REPO))
    from __graft_entry__ import _preloaded_state
    from dmclock_tpu.engine.fastpath import (scan_calendar_epoch,
                                             scan_prefix_epoch)
    from profile_util import scalar_latency, state_digest

    lat = scalar_latency()

    def limited_state():
        st = _preloaded_state(100_000, 256, ring=256)
        n = 100_000
        # tight limits: limit tags already past `now`, so Wait would
        # park everyone and Allow limit-breaks every serve
        return st._replace(
            limit_inv=jnp.full((n,), 10**6, dtype=jnp.int64),
            head_limit=jnp.full((n,), 10**12, dtype=jnp.int64),
            head_ready=jnp.zeros((n,), dtype=bool))

    rows = []
    # sorted flat epochs, Allow
    run = jax.jit(functools.partial(
        scan_prefix_epoch, m=21, k=49152, anticipation_ns=0,
        allow_limit_break=True), donate_argnums=(0,))
    st = limited_state()
    ep = run(st, jnp.int64(0))
    jax.device_get(state_digest(ep.state))
    lb = bool(jax.device_get(ep.lb).any())
    st = limited_state()
    t0 = time.perf_counter()
    total = 0
    for _ in range(3):
        ep = run(st, jnp.int64(0))
        st = ep.state
        total += int(jax.device_get(ep.count).sum())
    jax.device_get(state_digest(st))
    wall = time.perf_counter() - t0 - lat
    rows.append(("Allow limit-break (sorted flat epochs)",
                 total / wall, lb))
    print(f"allow sorted: {total/wall/1e6:.1f} M dec/s (lb={lb})")

    # calendar epochs, Allow.  The epoch output has no lb aggregate,
    # so verify limit-breaks actually fire via one calendar_batch on
    # the same state (a classification regression must not let this
    # row silently measure something else).
    from dmclock_tpu.engine.fastpath import calendar_batch
    b = calendar_batch(limited_state(), jnp.int64(0), steps=8,
                       anticipation_ns=0, allow_limit_break=True)
    lb_cal = bool(jax.device_get(b.lb).sum() > 0)
    assert lb_cal, "calendar Allow row: no limit-breaks fired"
    run = jax.jit(functools.partial(
        scan_calendar_epoch, m=8, steps=8, anticipation_ns=0,
        allow_limit_break=True), donate_argnums=(0,))
    st = limited_state()
    ep = run(st, jnp.int64(0))
    jax.device_get(state_digest(ep.state))
    st = limited_state()
    t0 = time.perf_counter()
    total = 0
    for _ in range(6):
        ep = run(st, jnp.int64(0))
        st = ep.state
        total += int(jax.device_get(ep.count).sum())
    jax.device_get(state_digest(st))
    wall = time.perf_counter() - t0 - lat
    rows.append(("Allow limit-break (calendar epochs)",
                 total / wall, lb_cal))
    print(f"allow calendar: {total/wall/1e6:.1f} M dec/s")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-native", action="store_true")
    ap.add_argument("--skip-tpu", action="store_true")
    ap.add_argument("--regimes", action="store_true",
                    help="also run the regime-coverage sweep")
    ap.add_argument("--devsim", action="store_true",
                    help="also run the device-sim closed-loop headline")
    ap.add_argument("--calibrate", action="store_true",
                    help="also run the cfg4 reservation calibration "
                    "study (slow: ~9 sustained runs)")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--calendar", action="store_true",
                    help="round-5 calendar-engine + Allow-regime rows "
                         "(prints; paste into RESULTS.md)")
    args = ap.parse_args()

    if args.calendar:
        tpu_calendar_sweep()
        tpu_allow_regime_row()
        return

    here = Path(__file__).resolve().parent
    native_part = here / ".native_section.md"
    tpu_part = here / ".tpu_section.md"
    regime_part = here / ".regime_section.md"
    sustained_part = here / ".sustained_section.md"
    devsim_part = here / ".devsim_section.md"
    calib_part = here / ".calib_section.md"

    if not args.skip_native:
        lines = ["## Native heap K-sweep (dmc_sim_100_100.conf, "
                 f"mean of {args.repeat} runs)", "",
                 "| K | add_request ns | sim wall s |", "|---|---|---|"]
        for k, add, wall in native_k_sweep(args.repeat):
            lines.append(f"| {k} | {add:.0f} | {wall:.2f} |")
        lines.append("")
        native_part.write_text("\n".join(lines))
    if not args.skip_tpu:
        import jax
        plat = jax.devices()[0].platform
        lines = [f"## TPU prefix-epoch k/m sweep (100k clients, "
                 f"platform={plat})", "",
                 "| k | m | M dec/s | fill |", "|---|---|---|---|"]
        for k, m, dps, fill in tpu_km_sweep():
            lines.append(f"| {k} | {m} | {dps/1e6:.2f} | {fill:.3f} |")
        lines.append("")
        tpu_part.write_text("\n".join(lines))
    if args.regimes:
        lines = ["## Regime coverage (prefix engine, 100k clients, "
                 "k=49152, m=21)", "",
                 "| scenario | M dec/s | fill |", "|---|---|---|"]
        for name, dps, fill in tpu_regime_sweep():
            lines.append(f"| {name} | {dps/1e6:.2f} | {fill:.3f} |")
        lines.append("")
        regime_part.write_text("\n".join(lines))
        lines = ["## Sustained closed loop, arrivals included "
                 "(BASELINE configs #3/#4)", "",
                 "| workload | M dec/s | fill | resv phase | mean "
                 "depth |", "|---|---|---|---|---|"]
        for name, r in tpu_sustained_sweep():
            lines.append(
                f"| {name} | {r['dps']/1e6:.2f} | {r['fill']:.3f} | "
                f"{r['resv_phase_frac']:.2f} | {r['mean_depth']:.0f} |")
        lines.append("")
        sustained_part.write_text("\n".join(lines))

    if args.calibrate:
        lines = ["## cfg4 reservation calibration (100k clients, Zipf, "
                 "k=49152 m=21, dt=50ms)", "",
                 "| design | resv rate /s | resv phase | fill | "
                 "M dec/s |", "|---|---|---|---|---|"]
        for name, r, out in cfg4_calibration_sweep():
            lines.append(
                f"| {name} | {r:.0f} | {out['resv_phase_frac']:.3f} | "
                f"{out['fill']:.3f} | {out['dps']/1e6:.1f} |")
        lines.append("")
        lines.append(
            "The share is monotone in the rate for every design: "
            "weight serves' reservation-debt reduction keeps mixed "
            "clients' reservation tags at the eligibility boundary, "
            "so the phases interleave per decision; the shipped cfg4 "
            "is mixed-staggered at the rate whose share is ~0.5 "
            "(bench.CFG4_RESV_RATE).")
        lines.append("")
        calib_part.write_text("\n".join(lines))

    if args.devsim:
        row = device_sim_headline()
        lines = ["## Device-sim closed loop (100k clients, prefix "
                 "serve q=4096, random selection, 2-thread servers, "
                 "one chip)", "",
                 "| M ops/s (wall) | total ops | virtual s | "
                 "weight 3:1 ratio |", "|---|---|---|---|",
                 f"| {row['ops_per_sec']/1e6:.2f} | "
                 f"{row['total_ops']} | {row['virtual_s']:.1f} | "
                 f"{row['weight_ratio_3_1']:.2f} |", ""]
        devsim_part.write_text("\n".join(lines))

    head = ["# Benchmark sweeps", "",
            "Produced by `python benchmark/run_sweeps.py` "
            "(see its docstring).", ""]
    body = [p.read_text() for p in (native_part, tpu_part, regime_part,
                                    sustained_part, devsim_part,
                                    calib_part)
            if p.exists()]
    RESULTS.write_text("\n".join(head + body))
    print(f"wrote {RESULTS}")


if __name__ == "__main__":
    main()
