"""Degraded-mode cluster stepping under an injected fault plan.

Wraps ``parallel.cluster`` with the graceful-degradation semantics the
reference protocol promises but the happy-path port never exercised:

- a **down** server commits nothing (engine and tracker counters keep
  last-good state; wall time still passes -- its virtual clock keeps
  tracking ``advance_ns`` but gains no serve-side advancement) and its
  decision slots read NONE; the psum still runs on every shard (SPMD),
  but a down shard's contribution is frozen at its last committed
  counters -- the global counters stay **monotone**, which is what
  makes the whole fault model protocol-safe;
- surviving servers keep serving their reservation contracts from
  whatever counter view they hold (``server_round`` takes the view as
  an argument -- the stale-counter tolerance of ``dmclock_client.h``);
- a **restarted** server re-syncs its ``TrackerState`` marks from the
  monotone global counters (:func:`resync_tracker`) before serving
  again, exactly like a real client re-contacting a returned server;
- every injected fault is counted into the on-device metrics vector
  (``server_dropouts`` / ``tracker_resyncs`` / ``faults_injected``
  rows) and the per-(server, client) conformance table
  (:func:`cluster_conformance`) mirrors the PR-1 sim table.

``fault=None`` takes the exact pre-existing ``cluster_step`` path --
zero cost when no faults are configured -- and an all-benign plan
(``faults.zero_plan``) is pinned bit-identical to ``None`` by the
chaos differential gate (``tests/test_robust.py``, ``scripts/ci.sh``).
"""

from __future__ import annotations

import functools
import hashlib
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..engine import kernels
from ..obs import device as obsdev
from ..parallel import cluster as CL
from ..parallel.cluster import SERVER_AXIS, ClusterState, server_round
from ..parallel.tracker import (BorrowTrackerState, borrow_tracker_track,
                                global_counters, tracker_track)
from .faults import FaultPlan, FaultStep, plan_step


class RobustClusterState(NamedTuple):
    """ClusterState plus the degradation bookkeeping.

    ``view_delta``/``view_rho`` are each server's *held* view of the
    global counters ([S, C] int64, re-synced on live non-delayed
    steps); ``up_prev`` tracks liveness transitions; ``metrics`` is a
    per-shard ``obs.device`` vector ([S, NUM_METRICS]; counters add,
    hwm rows max -- merge shards with ``obs.device.metrics_combine_np``
    or :func:`metrics_totals`)."""

    cluster: ClusterState
    view_delta: jnp.ndarray
    view_rho: jnp.ndarray
    up_prev: jnp.ndarray
    metrics: jnp.ndarray


def init_robust(cluster: ClusterState) -> RobustClusterState:
    """Wrap a freshly built cluster: views start at the protocol's
    counters-start-at-1 origin, every server up, metrics zero."""
    s, c = cluster.tracker.completed_delta.shape
    ones = jnp.ones((s, c), dtype=jnp.int64)
    return RobustClusterState(
        cluster=cluster, view_delta=ones, view_rho=ones,
        up_prev=jnp.ones((s,), dtype=bool),
        metrics=jnp.zeros((s, obsdev.NUM_METRICS), dtype=jnp.int64))


def shard_robust(rc: RobustClusterState, mesh) -> RobustClusterState:
    sharding = NamedSharding(mesh, P(SERVER_AXIS))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), rc)


def resync_tracker(tracker, g_delta: jnp.ndarray, g_rho: jnp.ndarray):
    """Re-mark a restarted shard's tracker state against the monotone
    global counters: the next request from each seen client carries
    delta/rho = (global movement since the resync) - (own completions
    here since the resync) -- the same forgiveness the reference's
    re-marking ``prepare_req`` applies, so nothing missed during the
    outage is double-charged.  Unseen clients are untouched (their
    first contact already gets ReqParams(1, 1))."""
    seen = tracker.seen
    if isinstance(tracker, BorrowTrackerState):
        return tracker._replace(
            prev_delta=jnp.where(seen, g_delta, tracker.prev_delta),
            prev_rho=jnp.where(seen, g_rho, tracker.prev_rho),
            borrow_delta=jnp.where(seen, 0, tracker.borrow_delta),
            borrow_rho=jnp.where(seen, 0, tracker.borrow_rho))
    return tracker._replace(
        last_mark_delta=jnp.where(
            seen, g_delta - tracker.completed_delta,
            tracker.last_mark_delta),
        last_mark_rho=jnp.where(
            seen, g_rho - tracker.completed_rho,
            tracker.last_mark_rho))


def _one_server_step_faulty(engine, tracker, now, arr, view_d, view_r,
                            up_prev, met, up, skew, delay, dup, *,
                            cost, decisions_per_step, anticipation_ns,
                            allow_limit_break, max_arrivals):
    """One server's degraded-mode round (inside shard_map, vmapped over
    the [1] shard axis; ``up``/``skew``/``delay``/``dup`` are this
    server's FaultStep scalars)."""
    # the collective runs on EVERY shard (SPMD); a down shard's
    # counters are frozen, so the psum stays monotone
    g_d, g_r = global_counters(
        tracker, lambda x: lax.psum(x, SERVER_AXIS))

    restart = up & ~up_prev
    dropout = ~up & up_prev

    # counter-view sync: live servers pull the fresh psum unless the
    # plan delays their piggyback updates; a restart always re-syncs
    sync = (up & ~delay) | restart
    view_d = jnp.where(sync, g_d, view_d)
    view_r = jnp.where(sync, g_r, view_r)

    # restarted shard re-marks its tracker against the global counters
    resynced = resync_tracker(tracker, view_d, view_r)
    tracker = jax.tree.map(
        lambda a, b: jnp.where(restart, a, b), resynced, tracker)

    # the round itself, against the held view and the skewed clock
    new_engine, new_tracker, new_now, decs = server_round(
        engine, tracker, now + skew, arr, cost, view_d, view_r,
        decisions_per_step=decisions_per_step,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        max_arrivals=max_arrivals)

    # duplicated completions: fold this step's completion batch a
    # second time (masked; an int scatter-add of 0 is exact)
    served = decs.type == kernels.RETURNING
    track = borrow_tracker_track \
        if isinstance(tracker, BorrowTrackerState) else tracker_track
    new_tracker = track(new_tracker, decs.slot, decs.cost, decs.phase,
                        served & dup)

    # commit gate: a down server keeps last-good state; its decision
    # slots read NONE (nothing was handed out)
    keep = lambda new, old: jnp.where(up, new, old)  # noqa: E731
    engine = jax.tree.map(keep, new_engine, engine)
    tracker = jax.tree.map(keep, new_tracker, tracker)
    now = jnp.where(up, new_now - skew, now)
    decs = kernels.Decision(
        type=jnp.where(up, decs.type, jnp.int32(kernels.NONE)),
        slot=jnp.where(up, decs.slot, jnp.int32(-1)),
        phase=jnp.where(up, decs.phase, jnp.int32(0)),
        cost=jnp.where(up, decs.cost, jnp.int64(0)),
        when=jnp.where(up, decs.when, jnp.int64(0)),
        limit_break=decs.limit_break & up)

    served = decs.type == kernels.RETURNING
    n_served = jnp.sum(served).astype(jnp.int64)
    n_resv = jnp.sum(served & (decs.phase == 0)).astype(jnp.int64)
    perturb = ((dup & up).astype(jnp.int64)
               + (delay & up).astype(jnp.int64)
               + ((skew != 0) & up).astype(jnp.int64))
    events = dropout.astype(jnp.int64) + restart.astype(jnp.int64)
    met = obsdev.metrics_combine(met, obsdev.metrics_delta(
        decisions=n_served, resv=n_resv, prop=n_served - n_resv,
        limit_break=jnp.sum(decs.limit_break).astype(jnp.int64),
        ring_hwm=jnp.max(engine.depth).astype(jnp.int64),
        server_dropouts=dropout.astype(jnp.int64),
        tracker_resyncs=restart.astype(jnp.int64),
        faults_injected=events + perturb))
    return engine, tracker, now, view_d, view_r, up, met, decs


def _merge_held_metrics(metrics: jnp.ndarray, mesh) -> jnp.ndarray:
    """Mesh-merge the [S, NUM_METRICS] held-view vectors in-graph
    (counters psum, hwm pmax); the result is replicated, one vector."""
    spec = P(SERVER_AXIS)
    fn = jax.shard_map(
        lambda m: obsdev.metrics_mesh_reduce(
            obsdev.metrics_combine_axis(m), SERVER_AXIS),
        mesh=mesh, in_specs=(spec,), out_specs=P(),
        check_vma=False)
    return fn(metrics)


def robust_cluster_step(rc: RobustClusterState, arrivals: jnp.ndarray,
                        cost, mesh, *,
                        fault: Optional[FaultStep] = None,
                        decisions_per_step: int,
                        max_arrivals: int = 1,
                        anticipation_ns: int = 0,
                        allow_limit_break: bool = False,
                        advance_ns: int = 0,
                        with_merged: bool = False,
                        with_pressure: bool = False):
    """One cluster step under an optional :class:`FaultStep`.

    ``fault=None`` (STATIC) delegates to the plain ``cluster_step`` --
    the fault plumbing costs nothing when unused, and the views /
    transition bookkeeping are untouched (they re-sync on the next
    faulty step).  Pure; jit with ``mesh``/config bound via partial.

    ``with_merged`` (STATIC) additionally returns the mesh-merged
    total of the per-shard held-view metric vectors -- counters psum,
    hwm rows pmax via ``obs.device.metrics_mesh_reduce``, the same
    in-graph collective the healthy path's
    ``cluster_step(with_metrics=True)`` got in PR-4 -- replicated
    across the mesh, so cluster fault totals need no host gather even
    mid-chaos.  Pinned merged == host-summed under a nonzero plan in
    ``tests/test_cluster_realism.py``.

    ``with_pressure`` (STATIC) additionally returns ``(per_shard
    int64[S, PRESS_FIELDS], merged int64[PRESS_FIELDS])`` post-round
    scheduling-pressure vectors (``obs.provenance.pressure_vec`` --
    eligible depth / backlog / peak / head-wait watermark) through the
    same psum/pmax collective: the degraded-mode twin of the healthy
    path's gauges, so the rack-scheduling placement signal stays
    published even mid-chaos (a down shard reports its FROZEN state:
    its backlog keeps aging, which is exactly what a router must see).
    """
    if fault is None:
        out = CL.cluster_step(
            rc.cluster, arrivals, cost, mesh,
            decisions_per_step=decisions_per_step,
            max_arrivals=max_arrivals, anticipation_ns=anticipation_ns,
            allow_limit_break=allow_limit_break, advance_ns=advance_ns,
            with_pressure=with_pressure)
        cluster, decs = out[0], out[1]
        rc = rc._replace(cluster=cluster)
        res = (rc, decs)
        if with_merged:
            # no fault plumbing ran, but the caller still wants the
            # merged view of the HELD metrics (frozen this step)
            res = res + (_merge_held_metrics(rc.metrics, mesh),)
        if with_pressure:
            res = res + tuple(out[2:])
        return res

    cost = jnp.asarray(cost, dtype=jnp.int64)
    f_up = jnp.asarray(fault.up, dtype=bool)
    f_skew = jnp.asarray(fault.skew_ns, dtype=jnp.int64)
    f_delay = jnp.asarray(fault.delay_counters, dtype=bool)
    f_dup = jnp.asarray(fault.dup_completions, dtype=bool)

    def shard_fn(engine, tracker, now, arr, view_d, view_r, up_prev,
                 met, up, skew, delay, dup):
        step = functools.partial(
            _one_server_step_faulty, cost=cost,
            decisions_per_step=decisions_per_step,
            anticipation_ns=anticipation_ns,
            allow_limit_break=allow_limit_break,
            max_arrivals=max_arrivals)
        out = jax.vmap(step)(engine, tracker, now, arr, view_d,
                             view_r, up_prev, met, up, skew, delay,
                             dup)
        if with_merged:
            # local reduce over this shard's servers, then the mesh
            # collective: counters psum, hwm pmax (associative +
            # commutative, so mesh order cannot matter)
            merged = obsdev.metrics_mesh_reduce(
                obsdev.metrics_combine_axis(out[6]), SERVER_AXIS)
            out = out + (merged,)
        if with_pressure:
            from ..obs import provenance as obsprov
            # post-round engine state at the UNSKEWED clock (out[2]):
            # a down shard's frozen backlog keeps aging against the
            # cluster clock, exactly what a router must see
            press = jax.vmap(obsprov.pressure_vec)(out[0], out[2])
            out = out + (press, obsprov.pressure_mesh_reduce(
                obsprov.pressure_combine_axis(press), SERVER_AXIS))
        return out

    spec = P(SERVER_AXIS)
    out_specs = (spec,) * 8 + ((P(),) if with_merged else ())
    if with_pressure:
        out_specs += (spec, P())
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec,) * 12, out_specs=out_specs,
        check_vma=False)
    now0 = rc.cluster.now + jnp.int64(advance_ns)
    outs = fn(
        rc.cluster.engine, rc.cluster.tracker, now0, arrivals,
        rc.view_delta, rc.view_rho, rc.up_prev, rc.metrics,
        f_up, f_skew, f_delay, f_dup)
    engine, tracker, now, view_d, view_r, up_prev, met, decs = \
        outs[:8]
    rc = RobustClusterState(
        cluster=ClusterState(engine=engine, tracker=tracker, now=now),
        view_delta=view_d, view_rho=view_r, up_prev=up_prev,
        metrics=met)
    return (rc, decs) + tuple(outs[8:])


# Module-level jit cache (the engine/queue.py _JIT_CACHE convention):
# a fresh jax.jit(partial(...)) per run_with_plan call would recompile
# the whole shard_map cluster program for every run of identical
# static config -- the CI chaos smoke alone runs three.  The cache
# keying (incl. the unhashable-mesh fallback) is shared with the
# healthy-path driver: parallel.cluster.mesh_step_jit.
_STEP_JIT_CACHE: dict = {}


def _jit_step(mesh, cfg: tuple):
    return CL.mesh_step_jit(_STEP_JIT_CACHE, robust_cluster_step,
                            mesh, cfg)


def run_with_plan(rc: RobustClusterState, arrivals, cost, mesh,
                  plan: Optional[FaultPlan] = None, *,
                  decisions_per_step: int, max_arrivals: int = 1,
                  anticipation_ns: int = 0,
                  allow_limit_break: bool = False,
                  advance_ns: int = 0, tracer=None):
    """Drive ``arrivals.shape[0]`` cluster steps under ``plan`` (None =
    no fault plumbing at all).  Returns ``(rc, decs_seq)`` with the
    per-step decisions fetched to host numpy -- the stream the chaos
    digest and the conformance table are computed from.

    ``tracer`` (``obs.spans.SpanTracer`` or None) records one
    ``cluster.round`` dispatch span per step (the whole-mesh launch;
    args carry the step index and whether a fault was applied) and a
    ``cluster.fetch`` span for the decision readback -- host-side
    only, the decision stream is bit-identical either way."""
    from ..obs import spans as _spans

    step = _jit_step(mesh, (decisions_per_step, max_arrivals,
                            anticipation_ns, allow_limit_break,
                            advance_ns))
    decs_seq = []
    for t in range(np.asarray(arrivals).shape[0]):
        fault = plan_step(plan, t) if plan is not None else None
        with _spans.span(tracer, "cluster.round", "dispatch",
                         step=t, faulty=fault is not None):
            rc, decs = step(rc, jnp.asarray(arrivals[t]), cost,
                            fault=fault)
        with _spans.span(tracer, "cluster.fetch", "fetch", step=t):
            decs_seq.append(jax.device_get(decs))
    return rc, decs_seq


def effective_plan(plan: FaultPlan, counter_sync_every: int = 1,
                   round0: int = 0) -> FaultPlan:
    """Fold the ``counter_sync_every`` staleness grid into a plan's
    ``delay_counters`` mask: a non-sync round IS the delay fault (the
    PR-13 equivalence -- the knob is the stale-view tolerance turned
    into a cadence), so the host loop under the effective plan is the
    exact reference for a fused K-grid launch under the raw plan.  At
    K=1 the plan is returned unchanged."""
    sync = CL.round_sync_mask(plan.steps, counter_sync_every, round0)
    if sync.all():
        return plan
    return plan._replace(
        delay_counters=plan.delay_counters | ~sync[:, None])


def run_mesh_rounds_with_plan(rc: RobustClusterState, arrivals_seq,
                              cost, mesh, plan: FaultPlan, *,
                              decisions_per_step: int,
                              max_arrivals: int = 1,
                              anticipation_ns: int = 0,
                              allow_limit_break: bool = False,
                              advance_ns: int = 0,
                              counter_sync_every: int = 1,
                              round0: int = 0):
    """The CHAOS twin of ``parallel.cluster.run_mesh_rounds``: ONE
    ``shard_map`` launch advances every server by ``E`` whole degraded
    rounds -- a ``lax.scan`` over :func:`_one_server_step_faulty`, the
    SAME per-round program the host loop (:func:`run_with_plan`) jits
    per step -- with the seeded :class:`FaultPlan` riding the scan as
    traced per-round mask slices and the ``counter_sync_every``
    staleness grid folded into the delay mask
    (:func:`effective_plan`).  Dropout/restart/skew/dup semantics,
    tracker re-sync, the frozen-contribution monotone psum, and the
    per-shard fault metric rows are all byte-the-same construction as
    the host loop's, so the digest gate

    ``run_mesh_rounds_with_plan(plan, K) == run_with_plan(
    effective_plan(plan, K))``

    (decisions + held views + tracker state + metric vectors) is an
    identity of launch structure only: E round-trips collapse to one.
    Returns ``(rc, decs)`` with ``decs`` leaves ``[S, E, k]``
    (re-slice with ``parallel.cluster.mesh_decs_seq``)."""
    import functools

    arrivals_seq = jnp.asarray(arrivals_seq, dtype=jnp.int32)
    epochs = int(arrivals_seq.shape[0])
    cost = jnp.asarray(cost, dtype=jnp.int64)
    eff = effective_plan(plan, counter_sync_every, round0)
    assert eff.steps == epochs, (eff.steps, epochs)
    # [T, S] plan leaves -> [S, T] so P(servers) splits them
    f_up = jnp.asarray(np.ascontiguousarray(eff.up.T))
    f_skew = jnp.asarray(np.ascontiguousarray(eff.skew_ns.T))
    f_delay = jnp.asarray(np.ascontiguousarray(eff.delay_counters.T))
    f_dup = jnp.asarray(np.ascontiguousarray(eff.dup_completions.T))
    arr_s = jnp.swapaxes(arrivals_seq, 0, 1)
    adv = jnp.int64(advance_ns)

    step = functools.partial(
        _one_server_step_faulty, cost=cost,
        decisions_per_step=decisions_per_step,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        max_arrivals=max_arrivals)

    def per_server(engine, tracker, now, arrs, vd, vr, up_prev, met,
                   ups, skews, delays, dups):
        def body(carry, xs):
            engine, tracker, now, vd, vr, up_prev, met = carry
            arr, up, skew, delay, dup = xs
            engine, tracker, now, vd, vr, up_now, met, decs = step(
                engine, tracker, now + adv, arr, vd, vr, up_prev,
                met, up, skew, delay, dup)
            return (engine, tracker, now, vd, vr, up_now, met), decs

        carry, decs = lax.scan(
            body, (engine, tracker, now, vd, vr, up_prev, met),
            (arrs, ups, skews, delays, dups))
        engine, tracker, now, vd, vr, up_prev, met = carry
        return engine, tracker, now, vd, vr, up_prev, met, decs

    def shard_fn(engine, tracker, now, arrs, vd, vr, up_prev, met,
                 ups, skews, delays, dups):
        return jax.vmap(per_server)(engine, tracker, now, arrs, vd,
                                    vr, up_prev, met, ups, skews,
                                    delays, dups)

    spec = P(SERVER_AXIS)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec,) * 12,
                   out_specs=(spec,) * 8, check_vma=False)
    engine, tracker, now, vd, vr, up_prev, met, decs = fn(
        rc.cluster.engine, rc.cluster.tracker, rc.cluster.now, arr_s,
        rc.view_delta, rc.view_rho, rc.up_prev, rc.metrics,
        f_up, f_skew, f_delay, f_dup)
    rc = RobustClusterState(
        cluster=ClusterState(engine=engine, tracker=tracker, now=now),
        view_delta=vd, view_rho=vr, up_prev=up_prev, metrics=met)
    return rc, decs


def decision_digest(decs_seq) -> str:
    """sha256 over the decision stream (type/slot/phase/cost per step)
    -- the bit-identity currency of the chaos differential gate."""
    h = hashlib.sha256()
    for d in decs_seq:
        for arr in (d.type, d.slot, d.phase, d.cost):
            h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
    return h.hexdigest()


def metrics_totals(rc: RobustClusterState) -> dict:
    """Merge the per-shard metric vectors (counters add, hwm max) and
    name the rows -- one device fetch."""
    vecs = np.asarray(jax.device_get(rc.metrics))
    acc = np.zeros((obsdev.NUM_METRICS,), dtype=np.int64)
    acc = obsdev.metrics_combine_np(acc, *vecs)
    return obsdev.metrics_dict(acc)


# ----------------------------------------------------------------------
# per-(server, client) conformance -- the PR-1 table at cluster scale
# ----------------------------------------------------------------------

def cluster_conformance(decs_seq, arrivals, plan, qos_triples,
                        advance_ns: int, tol: float = 0.05
                        ) -> List[dict]:
    """Per-(server, client) QoS conformance over each server's LIVE
    window: delivered rate vs min(reservation, demand) and the limit
    cap -- the same verdict semantics as ``SimReport.conformance``
    (arrivals posted to a down server are lost, so they leave its
    demand).  ``qos_triples`` is [(reservation, weight, limit)] per
    client; each step spans ``advance_ns`` of virtual time."""
    arrivals = np.asarray(arrivals)
    t_steps, n_servers, n_clients = arrivals.shape
    live = np.asarray(plan.up) if plan is not None else \
        np.ones((t_steps, n_servers), dtype=bool)
    served = np.zeros((n_servers, n_clients), dtype=np.int64)
    for t, d in enumerate(decs_seq):
        dtype = np.asarray(d.type)
        dslot = np.asarray(d.slot)
        for s in range(n_servers):
            sel = dslot[s][dtype[s] == kernels.RETURNING]
            np.add.at(served[s], sel, 1)
    demand = (arrivals * live[:, :, None]).sum(axis=0)
    rows = []
    for s in range(n_servers):
        window_s = max(live[:, s].sum() * advance_ns / 1e9, 1e-9)
        for c in range(n_clients):
            resv, weight, limit = qos_triples[c]
            rate = served[s, c] / window_s
            demand_rate = demand[s, c] / window_s
            resv_floor = min(resv, demand_rate)
            rows.append({
                "server": s, "client": c,
                "live_steps": int(live[:, s].sum()),
                "reservation": resv, "weight": weight, "limit": limit,
                "ops": int(served[s, c]), "rate": rate,
                "demand_rate": demand_rate,
                "resv_met": (rate >= resv_floor * (1.0 - tol))
                if resv > 0 else True,
                "limit_ok": (rate <= limit * (1.0 + tol))
                if limit > 0 else True,
            })
    return rows


def format_cluster_conformance(rows: List[dict]) -> str:
    """Text table over :func:`cluster_conformance` rows (the PR-1
    conformance table with a server column and live-window rates)."""
    lines = ["-- per-(server, client) QoS conformance "
             "(live window) --",
             f"{'srv':>4} {'client':>6} {'live':>5} {'resv':>8} "
             f"{'limit':>8} {'ops':>8} {'rate':>9} {'demand':>9} "
             f"{'verdict':>12}"]
    for r in rows:
        verdict = ("ok" if r["resv_met"] else "RESV-MISS") + \
            ("" if r["limit_ok"] else "+LIMIT-EXCESS")
        lines.append(
            f"{r['server']:>4} {r['client']:>6} {r['live_steps']:>5} "
            f"{r['reservation']:>8.1f} {r['limit']:>8.1f} "
            f"{r['ops']:>8} {r['rate']:>9.2f} "
            f"{r['demand_rate']:>9.2f} {verdict:>12}")
    misses = sum(1 for r in rows if not r["resv_met"])
    excess = sum(1 for r in rows if not r["limit_ok"])
    lines.append(f"rows {len(rows)} | reservation misses {misses} "
                 f"| limit excesses {excess}")
    return "\n".join(lines)
