"""Device-resident batch-synchronous QoS simulator.

The SURVEY's "sharded batch sim" (parallelism table, SURVEY.md section
2) as a user-facing model: the ENTIRE closed loop -- client load
generation, the delta/rho piggyback protocol, dmClock scheduling, and
service completion -- lives on device, with servers as a mesh axis and
clients vmapped, so one program advances a whole multi-server cluster
thousands of operations per launch.  The host only drives slice chunks
and reads back aggregate stats.

This is deliberately a DIFFERENT model from the discrete-event host
harness (``sim.harness``), trading event-exact timing for compiled
throughput:

- Time advances in fixed slices of ``q * op_time`` ns; a server with
  backlog serves exactly ``q`` requests per slice (its iops rate), and
  every serve in a slice is stamped at the slice boundary.
- A client's sends for a slice are computed from its rate gap and
  window at the slice start; completions feed back with one-slice
  latency (outstanding decreases at the end of the slice that served
  them).
- Server selection: the harness's deterministic policy
  (``Simulation._make_server_select`` non-random branch), or -- with
  ``server_random_selection`` -- a device-side counter RNG
  (splitmix64 hash of (client, send-sequence), reference random policy
  ``simulate.h:401-444``): stateless, reproducible, identical on every
  shard.
- Multi-thread servers serve ``threads * q`` requests per slice (the
  harness's aggregate-rate model: op_time = threads/iops,
  ``sim_server.h:136-139``).

QoS semantics (tags, phases, AtLimit, idle-reactivation, the tracker
algebra) are exactly the engine's -- inherited from ``kernels.ingest``
/ ``engine_run`` and ``parallel.tracker``, the same kernels pinned by
the oracle differential suites.  Behavioral validation:
``tests/test_device_sim.py`` checks weight-proportional shares,
reservation floors, limit caps, and determinism.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import NS_PER_SEC, ClientInfo
from ..engine import kernels
# module-level on purpose: importing fastpath inside a traced function
# would stage its module-level jnp constants into the caller's trace
# (cached in module globals -> UnexpectedTracerError on reuse)
from ..engine.fastpath import (_window_heads, calendar_batch,
                               calendar_batch_bucketed,
                               calendar_batch_wheel, ring_window,
                               speculate_prefix_batch)
from ..engine.state import EngineState, init_state
from ..parallel.cluster import SERVER_AXIS, make_mesh
from ..parallel.tracker import (TrackerState, global_counters,
                                init_tracker, tracker_prepare,
                                tracker_track, tracker_track_counts)
from .config import SimConfig


class ClientLoad(NamedTuple):
    """Replicated ([C]) load-generator state, identical on every shard
    (updates derive from psum'd quantities, keeping shards in step)."""

    gap_ns: jnp.ndarray        # int64[C] inter-send gap
    next_send: jnp.ndarray     # int64[C] next send time (TIME-like ns)
    sent: jnp.ndarray          # int32[C] requests sent so far
    total_ops: jnp.ndarray     # int32[C]
    outstanding: jnp.ndarray   # int32[C]
    window: jnp.ndarray        # int32[C] max outstanding
    cost: jnp.ndarray          # int64[C]
    sel_base: jnp.ndarray      # int32[C] server-select base offset
    sel_range: jnp.ndarray     # int32[C] server-select range


class DeviceSim(NamedTuple):
    engine: EngineState        # [S, ...]
    tracker: TrackerState      # [S, C]
    load: ClientLoad           # [C] replicated
    served_resv: jnp.ndarray   # int64[S, C] completions by phase
    served_prop: jnp.ndarray   # int64[S, C]
    last_served: jnp.ndarray   # int64[S, C] slice-end of last completion
    t: jnp.ndarray             # int64 slice-aligned clock (scalar)
    guard_trips: jnp.ndarray   # int32 scalar: prefix rebase-guard trips
    #                            (must stay 0 -- init_device_sim
    #                            validates the only dynamic inputs;
    #                            run_device_sim raises otherwise)


@dataclass
class DeviceSimSpec:
    """Static launch parameters derived from a SimConfig."""

    n_servers: int
    n_clients: int
    op_time_ns: int            # uniform across servers
    q_per_slice: int           # serves per server per slice
    max_sends: int             # per client per slice (static bound)
    slice_ns: int
    allow_limit_break: bool
    all_weights_positive: bool = True  # Allow-fastpath restriction
    random_select: bool = False
    force_scan: bool = False   # test hook: disable the prefix serve
    select_impl: str = "sort"  # prefix selection backend
    #                            ("sort"|"radix"; bit-identical
    #                            decisions -- fastpath select_impl)
    calendar_impl: Optional[str] = None  # None = prefix/scan serving
    #                            only; "minstop"|"bucketed" front-loads
    #                            each slice with sortless calendar
    #                            batches (whole batches only, budget-
    #                            gated; the capped prefix loop finishes
    #                            the slice), so skewed populations
    #                            serve without the per-batch sort --
    #                            service is EXACTLY the q-step serial
    #                            stream either way
    calendar_steps: int = 8    # per-client serve budget per calendar
    #                            batch (<= ring_capacity)
    ladder_levels: int = 4     # fused ladder levels ("bucketed")


def _make_spec(cfg: SimConfig, q_per_slice: int = 4) -> DeviceSimSpec:
    iops = {g.server_iops for g in cfg.srv_group}
    threads = {g.server_threads for g in cfg.srv_group}
    assert len(iops) == 1 and len(threads) == 1, \
        "device_sim: uniform server groups (iops and threads)"
    n_servers = sum(g.server_count for g in cfg.srv_group)
    n_clients = sum(g.client_count for g in cfg.cli_group)
    n_threads = threads.pop()
    # aggregate service rate stays iops: T threads each at op_time =
    # T/iops (sim_server.h:136-139) -> T*q serves per q*op_time slice
    op_time_ns = int(0.5 + n_threads * 1e6 / iops.pop()) * 1000
    slice_ns = op_time_ns * q_per_slice
    q_per_slice = q_per_slice * n_threads
    # static bound on sends per client per slice; refuse configs whose
    # offered load cannot be expressed (a silent clamp would misreport
    # a simulator artifact as a QoS limit)
    min_gap = min(int(0.5 + 1e6 / g.client_iops_goal) * 1000
                  for g in cfg.cli_group)
    max_sends = max(1, slice_ns // max(min_gap, 1) + 1)
    assert max_sends <= 16, (
        f"client iops goals need {max_sends} sends/client/slice; the "
        "wave unroll caps at 16 -- raise server_iops (shorter slices) "
        "or lower client_iops_goal")
    return DeviceSimSpec(
        n_servers=n_servers, n_clients=n_clients,
        op_time_ns=op_time_ns, q_per_slice=q_per_slice,
        max_sends=max_sends, slice_ns=slice_ns,
        allow_limit_break=cfg.server_soft_limit,
        all_weights_positive=all(g.client_weight > 0
                                 for g in cfg.cli_group),
        random_select=cfg.server_random_selection)


def init_device_sim(cfg: SimConfig, ring_capacity: int = 256,
                    select_impl: str = "sort",
                    calendar_impl: Optional[str] = None,
                    calendar_steps: int = 8,
                    ladder_levels: int = 4
                    ) -> tuple[DeviceSim, DeviceSimSpec]:
    assert calendar_impl in (None, "minstop", "bucketed",
                             "wheel"), calendar_impl
    assert 1 <= calendar_steps <= ring_capacity, \
        "calendar_steps must fit the ring window"
    assert ladder_levels >= 1
    spec = _make_spec(cfg)
    spec.select_impl = select_impl
    spec.calendar_impl = calendar_impl
    spec.calendar_steps = calendar_steps
    spec.ladder_levels = ladder_levels
    s, c = spec.n_servers, spec.n_clients
    max_window = max(g.client_outstanding_ops for g in cfg.cli_group)
    assert max_window <= ring_capacity, (
        f"client_outstanding_ops {max_window} can exceed a per-client "
        f"ring of {ring_capacity}; raise ring_capacity")
    # the prefix serve path's rebase guards depend on request cost and
    # creation-order spread; both are static here (costs from config,
    # order = arange(C) fixed at init), so validating cost once makes a
    # guard failure impossible by construction -- the serve loop relies
    # on this to skip the per-batch guards_ok check
    max_cost = max(g.client_req_cost for g in cfg.cli_group)
    assert 0 < max_cost < (1 << 31), (
        f"client_req_cost {max_cost} overflows the int32 sort payload "
        "of the prefix serve path")

    infos, gaps, waits, totals, windows, costs, ranges = \
        [], [], [], [], [], [], []
    for g in cfg.cli_group:
        for _ in range(g.client_count):
            infos.append(ClientInfo(g.client_reservation,
                                    g.client_weight, g.client_limit))
            gaps.append(int(0.5 + 1e6 / g.client_iops_goal) * 1000)
            waits.append(int(g.client_wait_s * NS_PER_SEC))
            totals.append(g.client_total_ops)
            windows.append(g.client_outstanding_ops)
            costs.append(g.client_req_cost)
            ranges.append(min(g.client_server_select_range, s))

    factor = s / max(1, c)
    sel_base = np.asarray([int(0.5 + i * factor) % s for i in range(c)],
                          dtype=np.int32)

    one = init_state(c, ring_capacity)
    engine = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (s,) + a.shape), one)
    engine = engine._replace(
        active=jnp.ones((s, c), dtype=bool),
        order=jnp.broadcast_to(jnp.arange(c, dtype=jnp.int64), (s, c)),
        resv_inv=jnp.broadcast_to(jnp.asarray(
            [i.reservation_inv_ns for i in infos], jnp.int64), (s, c)),
        weight_inv=jnp.broadcast_to(jnp.asarray(
            [i.weight_inv_ns for i in infos], jnp.int64), (s, c)),
        limit_inv=jnp.broadcast_to(jnp.asarray(
            [i.limit_inv_ns for i in infos], jnp.int64), (s, c)),
    )
    tracker = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (s,) + a.shape), init_tracker(c))
    load = ClientLoad(
        gap_ns=jnp.asarray(gaps, jnp.int64),
        next_send=jnp.asarray(waits, jnp.int64),
        sent=jnp.zeros((c,), jnp.int32),
        total_ops=jnp.asarray(totals, jnp.int32),
        outstanding=jnp.zeros((c,), jnp.int32),
        window=jnp.asarray(windows, jnp.int32),
        cost=jnp.asarray(costs, jnp.int64),
        sel_base=jnp.asarray(sel_base),
        sel_range=jnp.asarray(ranges, jnp.int32),
    )
    sim = DeviceSim(engine=engine, tracker=tracker, load=load,
                    served_resv=jnp.zeros((s, c), jnp.int64),
                    served_prop=jnp.zeros((s, c), jnp.int64),
                    last_served=jnp.zeros((s, c), jnp.int64),
                    t=jnp.int64(0),
                    guard_trips=jnp.int32(0))
    return sim, spec


def shard_device_sim(sim: DeviceSim, mesh: Mesh) -> DeviceSim:
    srv = NamedSharding(mesh, P(SERVER_AXIS))
    rep = NamedSharding(mesh, P())
    return DeviceSim(
        engine=jax.tree.map(lambda a: jax.device_put(a, srv), sim.engine),
        tracker=jax.tree.map(lambda a: jax.device_put(a, srv),
                             sim.tracker),
        load=jax.tree.map(lambda a: jax.device_put(a, rep), sim.load),
        served_resv=jax.device_put(sim.served_resv, srv),
        served_prop=jax.device_put(sim.served_prop, srv),
        last_served=jax.device_put(sim.last_served, srv),
        t=jax.device_put(sim.t, rep),
        guard_trips=jax.device_put(sim.guard_trips, rep),
    )


def _slice_sends(load: ClientLoad, t0, slice_ns: int, max_sends: int):
    """How many sends each client performs this slice (bounded by rate,
    window, and remaining ops), all from slice-start state.

    Model bound: a client catching up after a window stall emits at
    most ``max_sends`` per slice even if its rate debt is larger (the
    wave unroll is static); the debt carries over via ``next_send``, so
    offered load is deferred, never lost.  _make_spec's assert covers
    the steady-state rate; this bound only shapes post-stall bursts."""
    t_end = t0 + slice_ns
    by_rate = jnp.where(
        load.next_send < t_end,
        ((t_end - load.next_send) + load.gap_ns - 1) // load.gap_ns,
        0).astype(jnp.int32)
    n = jnp.minimum(jnp.minimum(by_rate, max_sends),
                    jnp.minimum(load.window - load.outstanding,
                                load.total_ops - load.sent))
    return jnp.maximum(n, 0)


def _splitmix64(x):
    """Stateless counter hash (splitmix64 finalizer): the device-side
    RNG for random server selection -- same value on every shard for a
    given (client, sequence), no carried RNG state."""
    x = (x + jnp.int64(-7046029254386353131))      # 0x9E3779B97F4A7C15
    z = x
    z = (z ^ (z >> 30)) * jnp.int64(-4658895280553007687)
    z = (z ^ (z >> 27)) * jnp.int64(-7723592293110705685)
    return z ^ (z >> 31)


def _sends_to_server(load: ClientLoad, n, wave: int, server_ids,
                     n_servers: int, random_select: bool):
    """Does client c's ``wave``-th send this slice target THIS server?
    Deterministic policy: (sel_base + seq % range) % n_servers; random
    policy: sel_base + hash(client, seq) % range (the reference picks
    uniformly within the client's server window, simulate.h:401-444).
    ``n_servers`` is the static GLOBAL count -- server_ids.shape[0]
    inside shard_map is only the local shard slice."""
    seq = load.sent + wave
    if random_select:
        c = seq.shape[0]
        h = _splitmix64(seq.astype(jnp.int64) * jnp.int64(1 << 20)
                        + jnp.arange(c, dtype=jnp.int64))
        pick = jnp.remainder(jnp.abs(h), load.sel_range.astype(jnp.int64))
        target = (load.sel_base + pick.astype(jnp.int32)) % n_servers
    else:
        target = (load.sel_base
                  + jnp.remainder(seq, load.sel_range)) % n_servers
    return (n > wave) & (target[None, :] == server_ids[:, None])


def device_sim_step(sim: DeviceSim, spec: DeviceSimSpec, mesh: Mesh,
                    slices: int) -> DeviceSim:
    """Advance ``slices`` time slices in one launch (jit this)."""
    s_total = spec.n_servers

    def shard_fn(engine, tracker, load, served_resv, served_prop,
                 last_served, t, trips, server_ids):
        def one_slice(carry, _):
            engine, tracker, load, sresv, sprop, slast, t, trips = carry
            # tracker is [S_local, C] inside the shard: the client-global
            # counters reduce over BOTH the local server slice and the
            # mesh axis
            g_delta, g_rho = global_counters(
                tracker, lambda x: lax.psum(x.sum(axis=0), SERVER_AXIS))

            n = _slice_sends(load, t, spec.slice_ns, spec.max_sends)
            c = n.shape[0]

            def ingest_wave(carry2, wave):
                engine, tracker = carry2
                mine = _sends_to_server(load, n, wave, server_ids,
                                        s_total, spec.random_select)

                def per_server(eng, trk, mine_row):
                    trk, d_out, r_out = tracker_prepare(
                        trk, mine_row, g_delta, g_rho)
                    # one request per client per wave, slots distinct:
                    # the vectorized wave ingest scales to 100k-client
                    # slices where the sequential op scan cannot
                    eng = kernels.ingest_wave(
                        eng, mine_row, t, load.cost,
                        jnp.where(mine_row, r_out, 1),
                        jnp.where(mine_row, d_out, 1),
                        anticipation_ns=0)
                    return eng, trk

                engine, tracker = jax.vmap(per_server)(engine, tracker,
                                                       mine)
                return (engine, tracker), None

            # python-unrolled waves (max_sends is static and small)
            for wave in range(spec.max_sends):
                (engine, tracker), _ = ingest_wave((engine, tracker),
                                                   wave)

            # serve q decisions per server at the slice boundary.
            # Large q (throughput shapes) uses prefix-commit batches:
            # sort-and-commit passes instead of a q-step serial scan.
            # A single batch serves each client at most once, so a
            # server whose eligible population is smaller than q
            # (select-range windows, drained/idle clients) would lose
            # the rest of its slice capacity; batches therefore LOOP --
            # each capped at the remaining slice budget, which keeps
            # the concatenated stream the exact serial prefix -- until
            # the budget is met or a batch commits nothing.
            # AtLimit::Allow rides the prefix path too (limit-break
            # candidates are a third unified class), PROVIDED every
            # client has weight > 0: a ready weight-0 client switches
            # the reference's Allow fallback to reservation order
            # globally, which per-client classification cannot express
            # (fastpath module docstring) -- that shape keeps the scan.
            t_end = t + spec.slice_ns
            # opting into the calendar serve path implies the budgeted
            # batch loop (it is exact at any q; the q >= 256 heuristic
            # only picks the default)
            use_prefix = ((spec.q_per_slice >= 256
                           or spec.calendar_impl is not None)
                          and (not spec.allow_limit_break
                               or spec.all_weights_positive)
                          and not spec.force_scan)
            use_cal = use_prefix and spec.calendar_impl is not None
            if spec.calendar_impl is not None and not use_cal:
                # refuse rather than silently A/B two identical
                # scan-path runs: the Allow-with-weight-0 shape (and
                # the force_scan test hook) cannot serve through the
                # batch loop at all (fastpath module docstring)
                raise ValueError(
                    "calendar_impl requires the batch serve loop: "
                    "incompatible with force_scan, and with "
                    "allow_limit_break unless every client weight "
                    "is positive")

            if use_prefix:
                q = spec.q_per_slice
                # the selection sort yields one row per client, so a
                # batch is at most n_clients wide; the loop covers q
                kb = min(q, spec.n_clients)

                def per_server_run(eng):
                    d0 = kernels.Decision(
                        type=jnp.full((q,), kernels.NONE, jnp.int32),
                        slot=jnp.full((q,), -1, jnp.int32),
                        phase=jnp.zeros((q,), jnp.int32),
                        cost=jnp.zeros((q,), jnp.int64),
                        when=jnp.zeros((q,), jnp.int64),
                        limit_break=jnp.zeros((q,), bool))

                    # --- calendar front-load (spec.calendar_impl):
                    # commit WHOLE sortless calendar batches while they
                    # fit the remaining slice budget -- each batch is an
                    # exact serial prefix, and a batch that would
                    # overshoot q is discarded untaken, so the capped
                    # prefix loop below finishes the slice exactly.
                    # Counts-only emission: the tracker and the stats
                    # fold per-client totals (tracker_track_counts).
                    cal_total = jnp.int32(0)
                    cal_srv = cal_rsv = None
                    if use_cal:
                        steps = min(spec.calendar_steps,
                                    eng.ring_capacity)
                        zc = jnp.zeros((spec.n_clients,), jnp.int32)

                        def cal_cond(carry):
                            return carry[4]

                        def cal_body(carry):
                            eng, srv, rsv, total, _ = carry
                            if spec.calendar_impl == "wheel":
                                b = calendar_batch_wheel(
                                    eng, t_end, steps=steps,
                                    levels=spec.ladder_levels,
                                    anticipation_ns=0,
                                    allow_limit_break=spec
                                    .allow_limit_break,
                                    use_pallas=False)
                            elif spec.calendar_impl == "bucketed":
                                b = calendar_batch_bucketed(
                                    eng, t_end, steps=steps,
                                    levels=spec.ladder_levels,
                                    anticipation_ns=0,
                                    allow_limit_break=spec
                                    .allow_limit_break,
                                    use_pallas=False)
                            else:
                                win = ring_window(eng, steps,
                                                  use_pallas=False)
                                b = calendar_batch(
                                    eng, t_end, steps=steps,
                                    anticipation_ns=0,
                                    allow_limit_break=spec
                                    .allow_limit_break,
                                    heads=(win.arr, win.cost))
                            ok = (b.count > 0) & \
                                (total + b.count <= q)
                            eng = jax.tree.map(
                                lambda new, old:
                                jnp.where(ok, new, old),
                                b.state, eng)
                            srv = srv + jnp.where(ok, b.served, 0)
                            rsv = rsv + jnp.where(ok, b.served_resv,
                                                  0)
                            total = (total
                                     + jnp.where(ok, b.count, 0)
                                     ).astype(jnp.int32)
                            return (eng, srv, rsv, total, ok)

                        eng, cal_srv, cal_rsv, cal_total, _ = \
                            lax.while_loop(
                                cal_cond, cal_body,
                                (eng, zc, zc, jnp.int32(0),
                                 jnp.bool_(True)))

                    def cond(carry):
                        _eng, total, last, _d, _gt = carry
                        return (total < q) & (last > 0)

                    def body(carry):
                        eng, total, _last, dbuf, gt = carry
                        # guards_ok cannot legitimately fail here: its
                        # only dynamic inputs (cost, creation-order
                        # spread) are static in this sim and validated
                        # at init_device_sim.  The trip counter makes
                        # that invariant CHECKED rather than assumed:
                        # run_device_sim raises if it ever goes
                        # nonzero (a future init_device_sim edit that
                        # weakens the validation would surface, not
                        # silently under-serve).
                        # The ring-head read forces the XLA rotate:
                        # this whole body runs under vmap (servers),
                        # which would add a grid to the gridless
                        # Pallas kernel.
                        heads = _window_heads(eng, ring_window(
                            eng, 1, use_pallas=False))
                        batch = speculate_prefix_batch(
                            eng, t_end, kb, anticipation_ns=0,
                            max_count=q - total, heads=heads,
                            allow_limit_break=spec.allow_limit_break,
                            select_impl=spec.select_impl)
                        gt = gt + jnp.where(batch.guards_ok, 0,
                                            1).astype(jnp.int32)
                        # pack the committed prefix at the buffer
                        # offset (invalid rows scatter out of range
                        # and drop; the buffer holds only the prefix-
                        # loop decisions -- calendar serves are folded
                        # as counts)
                        j = jnp.arange(kb, dtype=jnp.int32)
                        pos = jnp.where(j < batch.count,
                                        total - cal_total + j, q)
                        dbuf = jax.tree.map(
                            lambda buf, vals:
                            buf.at[pos].set(vals, mode="drop"),
                            dbuf, batch.decisions)
                        return (batch.state, total + batch.count,
                                batch.count, dbuf, gt)

                    eng, _total, _last, dbuf, gt = lax.while_loop(
                        cond, body,
                        (eng, cal_total, jnp.int32(1), d0,
                         jnp.int32(0)))
                    if use_cal:
                        return eng, dbuf, gt, cal_srv, cal_rsv
                    return eng, dbuf, gt

                if use_cal:
                    engine, decs, gts, cal_srv, cal_rsv = \
                        jax.vmap(per_server_run)(engine)
                else:
                    engine, decs, gts = jax.vmap(per_server_run)(
                        engine)
                trips = (trips + lax.psum(gts.sum(), SERVER_AXIS)
                         ).astype(jnp.int32)
            else:
                def per_server_run(eng):
                    eng, _, d = kernels.engine_run(
                        eng, t_end, spec.q_per_slice,
                        allow_limit_break=spec.allow_limit_break,
                        anticipation_ns=0, advance_now=False)
                    return eng, d

                engine, decs = jax.vmap(per_server_run)(engine)
            served = decs.type == kernels.RETURNING

            def per_server_track(trk, d_slot, d_cost, d_phase, d_srv):
                return tracker_track(trk, d_slot, d_cost, d_phase,
                                     d_srv)

            tracker = jax.vmap(per_server_track)(
                tracker, decs.slot, decs.cost, decs.phase, served)
            if use_cal:
                # calendar serves arrive as per-client totals; the
                # counts fold computes the same sums as the decision-
                # stream fold (per-client cost is constant here)
                tracker = jax.vmap(
                    lambda trk, s_, r_: tracker_track_counts(
                        trk, s_, r_, load.cost))(tracker, cal_srv,
                                                 cal_rsv)

            # stats + completion feedback (one [S_local, q] scatter-add
            # per phase; q is small)
            one = jnp.where(served, 1, 0).astype(jnp.int64)
            idx = jnp.where(served, decs.slot, 0)
            sresv = jax.vmap(lambda a, i, v: a.at[i].add(v))(
                sresv, idx, one * (decs.phase == 0))
            sprop = jax.vmap(lambda a, i, v: a.at[i].add(v))(
                sprop, idx, one * (decs.phase == 1))
            t_end_b = t + spec.slice_ns
            slast = jax.vmap(lambda a, i, v: a.at[i].max(v))(
                slast, idx, jnp.where(served, t_end_b, 0))
            done_here = jax.vmap(
                lambda i, v: jnp.zeros((c,), jnp.int32).at[i].add(
                    v.astype(jnp.int32)))(idx, one)
            if use_cal:
                sresv = sresv + cal_rsv.astype(jnp.int64)
                sprop = sprop + (cal_srv - cal_rsv).astype(jnp.int64)
                slast = jnp.maximum(
                    slast, jnp.where(cal_srv > 0, t_end_b,
                                     jnp.int64(0)))
                done_here = done_here + cal_srv
            completions = lax.psum(done_here.sum(axis=0), SERVER_AXIS)

            sends = n  # every shard computed the same [C] send counts
            load = load._replace(
                sent=(load.sent + sends).astype(jnp.int32),
                outstanding=(load.outstanding + sends
                             - completions).astype(jnp.int32),
                next_send=load.next_send
                + sends.astype(jnp.int64) * load.gap_ns,
            )
            return (engine, tracker, load, sresv, sprop, slast,
                    t_end, trips), None

        (engine, tracker, load, served_resv, served_prop, last_served,
         t, trips), _ = lax.scan(
            one_slice,
            (engine, tracker, load, served_resv, served_prop,
             last_served, t, trips), None, length=slices)
        return (engine, tracker, load, served_resv, served_prop,
                last_served, t, trips)

    srv = P(SERVER_AXIS)
    rep = P()
    server_ids = jnp.arange(s_total, dtype=jnp.int32)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(srv, srv, rep, srv, srv, srv, rep, rep, srv),
        out_specs=(srv, srv, rep, srv, srv, srv, rep, rep),
        check_vma=False)
    (engine, tracker, load, served_resv, served_prop, last_served, t,
     trips) = fn(sim.engine, sim.tracker, sim.load, sim.served_resv,
                 sim.served_prop, sim.last_served, sim.t,
                 sim.guard_trips, server_ids)
    return DeviceSim(engine=engine, tracker=tracker, load=load,
                     served_resv=served_resv, served_prop=served_prop,
                     last_served=last_served, t=t, guard_trips=trips)


def check_guard_trips(sim: DeviceSim) -> None:
    """Raise if any prefix batch tripped a rebase guard.  The guards'
    only dynamic inputs (request cost, creation-order spread) are
    validated statically by init_device_sim, so a trip means that
    validation no longer covers the workload and committed counts are
    untrustworthy."""
    trips = int(np.asarray(sim.guard_trips))
    if trips:
        raise RuntimeError(
            f"device_sim: {trips} prefix rebase-guard trip(s) -- "
            "init_device_sim's static validation no longer covers the "
            "workload (cost or creation-order spread past the int32 "
            "sort payload); committed counts are untrustworthy")


def run_device_sim(cfg: SimConfig, *, mesh: Optional[Mesh] = None,
                   ring_capacity: int = 256,
                   slices_per_launch: int = 64,
                   max_launches: int = 200,
                   check_guards: bool = True,
                   select_impl: str = "sort",
                   calendar_impl: Optional[str] = None,
                   calendar_steps: int = 8,
                   ladder_levels: int = 4):
    """Run to completion (all clients' ops served) or the launch cap.

    ``check_guards`` (default on) raises after any launch whose prefix
    batches tripped a rebase guard -- the invariant init_device_sim
    validates statically, made CHECKED so future edits that weaken the
    validation surface instead of silently under-serving.

    ``calendar_impl`` (None|"minstop"|"bucketed"|"wheel") front-loads
    each slice with sortless calendar batches
    (DeviceSimSpec.calendar_impl) -- service stays exactly the q-step
    serial stream, pinned by tests/test_calendar_bucketed.py and
    tests/test_calendar_wheel.py.

    Returns (sim, spec, report_str)."""
    if mesh is None:
        mesh = make_mesh()
        n_dev = len(mesh.devices.flat)
        # the servers axis must divide the device count; fall back to a
        # single device otherwise
        total = sum(g.server_count for g in cfg.srv_group)
        if total % n_dev != 0:
            mesh = make_mesh(1)
    sim, spec = init_device_sim(cfg, ring_capacity=ring_capacity,
                                select_impl=select_impl,
                                calendar_impl=calendar_impl,
                                calendar_steps=calendar_steps,
                                ladder_levels=ladder_levels)
    sim = shard_device_sim(sim, mesh)
    step = jax.jit(functools.partial(
        device_sim_step, spec=spec, mesh=mesh,
        slices=slices_per_launch), donate_argnums=(0,))
    total_ops = int(np.asarray(sim.load.total_ops).sum())
    launches = 0
    completed = 0
    for launches in range(1, max_launches + 1):
        sim = step(sim)
        if check_guards:
            check_guard_trips(sim)
        completed = int(np.asarray(sim.served_resv).sum()
                        + np.asarray(sim.served_prop).sum())
        if completed >= total_ops:
            break
    return sim, spec, format_report(cfg, sim, spec, launches,
                                    completed=completed,
                                    total_ops=total_ops)


def format_report(cfg: SimConfig, sim: DeviceSim, spec: DeviceSimSpec,
                  launches: int, *, completed: Optional[int] = None,
                  total_ops: Optional[int] = None) -> str:
    sresv = np.asarray(sim.served_resv).sum(axis=0)   # [C]
    sprop = np.asarray(sim.served_prop).sum(axis=0)
    t_s = int(sim.t) / NS_PER_SEC
    lines = ["=== device sim report ===",
             f"servers: {spec.n_servers}  clients: {spec.n_clients}  "
             f"slice: {spec.slice_ns} ns x {launches} launches",
             f"virtual duration: {t_s:.3f} s",
             f"total ops: {int(sresv.sum() + sprop.sum())} "
             f"(reservation {int(sresv.sum())}, "
             f"priority {int(sprop.sum())})"]
    last = np.asarray(sim.last_served).max(axis=0)  # [C]
    ci = 0
    for gi, g in enumerate(cfg.cli_group):
        sl = slice(ci, ci + g.client_count)
        ops = int(sresv[sl].sum() + sprop[sl].sum())
        finish_s = last[sl].max() / NS_PER_SEC
        rate = ops / finish_s / g.client_count if finish_s else 0.0
        lines.append(
            f"group {gi}: {g.client_count} clients  "
            f"r={g.client_reservation} w={g.client_weight} "
            f"l={g.client_limit} | ops {ops} "
            f"(res {int(sresv[sl].sum())} / prop {int(sprop[sl].sum())})"
            f" | done @ {finish_s:.2f}s | average {rate:.2f} ops/s")
        ci += g.client_count
    if completed is not None and total_ops is not None \
            and completed < total_ops:
        # partial runs must not read as converged QoS shares
        lines.append(f"INCOMPLETE: served {completed}/{total_ops} ops "
                     f"after {launches} launches (raise --max-launches)")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    from .config import parse_config_file

    p = argparse.ArgumentParser(
        prog="device_sim", description=__doc__.splitlines()[0])
    p.add_argument("-c", "--conf", required=True)
    p.add_argument("--ring-capacity", type=int, default=256)
    p.add_argument("--slices-per-launch", type=int, default=64)
    p.add_argument("--max-launches", type=int, default=200)
    args = p.parse_args(argv)
    cfg = parse_config_file(args.conf)
    _sim, _spec, report = run_device_sim(
        cfg, ring_capacity=args.ring_capacity,
        slices_per_launch=args.slices_per_launch,
        max_launches=args.max_launches)
    print(report)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
