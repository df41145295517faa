"""Mesh-sharded multi-server dmClock cluster.

The TPU-native replacement for the reference's multi-server simulation
(N ``SimulatedServer`` thread pools + callback "network",
``sim/src/test_dmclock_main.cc:146-188``): every server's scheduler
state is one shard of a stacked ``EngineState`` on the ``servers`` mesh
axis, the per-(server, client) completion counters live next to it, and
one ``cluster_step`` advances EVERY server by k scheduling decisions in
a single program -- with the dmClock wire protocol's global counters
computed as a ``psum`` over ICI (DCN across hosts, transparently, via
the same collective).

Layout notes (scaling-book recipe): pick the mesh, annotate shardings,
let XLA insert the collectives.  All arrays are sharded on the leading
``servers`` axis; the only cross-shard traffic is the [C]-sized psum of
completion counters -- exactly the four-scalar-per-request piggyback
contract, batched.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import kernels
from ..obs import device as obsdev
from ..engine.state import EngineState, init_state
from .tracker import (BorrowTrackerState, TrackerState,
                      borrow_tracker_prepare, borrow_tracker_track,
                      global_counters, init_borrow_tracker,
                      init_tracker, tracker_prepare, tracker_track)

SERVER_AXIS = "servers"


class ClusterState(NamedTuple):
    """Stacked per-server state; every leaf's leading axis is servers."""

    engine: EngineState       # [S, ...] scheduler state per server
    tracker: TrackerState     # [S, C] distributed-protocol counters
    #                           (TrackerState or BorrowTrackerState --
    #                           the accounting policy plug, reference
    #                           dmclock_client.h:39-154)
    now: jnp.ndarray          # int64[S] per-server virtual clock


def make_mesh(n_devices: int | None = None) -> Mesh:
    """A flat ``servers`` mesh over the first ``n_devices`` attached
    devices (all of them by default); asking for more than are
    attached is an error, never a smaller mesh."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"a {n_devices}-server mesh needs "
                             f"{n_devices} devices; {len(devs)} "
                             "attached")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (SERVER_AXIS,))


def init_cluster(n_servers: int, n_clients: int,
                 ring_capacity: int = 64,
                 tracker_kind: str = "orig") -> ClusterState:
    """Host-side construction: capacity ``n_clients`` slots per server
    (slot i == client i cluster-wide, which is what lets completion
    counters psum by position).  ``tracker_kind``: "orig" or
    "borrowing" (the reference's two accounting policies)."""
    one = init_state(n_clients, ring_capacity)
    engine = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_servers,) + a.shape), one)
    base = {"orig": init_tracker,
            "borrowing": init_borrow_tracker}[tracker_kind](n_clients)
    tracker = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_servers,) + a.shape), base)
    return ClusterState(engine=engine, tracker=tracker,
                        now=jnp.zeros((n_servers,), dtype=jnp.int64))


def shard_cluster(cluster: ClusterState, mesh: Mesh) -> ClusterState:
    """Place every leaf with its leading axis split over the servers
    mesh axis."""
    sharding = NamedSharding(mesh, P(SERVER_AXIS))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), cluster)


def install_clients(cluster: ClusterState, resv_inv, weight_inv,
                    limit_inv, active_mask=None) -> ClusterState:
    """Register the same client population on every server (QoS inverses
    are [C] int64 arrays).  Creation order = client index, making the
    cross-backend tie-break deterministic.  ``active_mask`` bool[C]
    restricts the initial population (slots left inactive join later
    via ``create_clients``); default: all C slots."""
    n_servers = cluster.now.shape[0]
    c = resv_inv.shape[0]
    if active_mask is None:
        active_mask = jnp.ones((c,), dtype=bool)

    def bcast(a):
        return jnp.broadcast_to(a, (n_servers, c))

    eng = cluster.engine._replace(
        active=bcast(active_mask),
        order=bcast(jnp.arange(c, dtype=jnp.int64)),
        resv_inv=bcast(resv_inv), weight_inv=bcast(weight_inv),
        limit_inv=bcast(limit_inv),
    )
    return cluster._replace(engine=eng)


def server_round(engine: EngineState, tracker: TrackerState,
                 now: jnp.ndarray, arrivals_per_client: jnp.ndarray,
                 cost: jnp.ndarray, g_delta: jnp.ndarray,
                 g_rho: jnp.ndarray, decisions_per_step: int,
                 anticipation_ns: int, allow_limit_break: bool,
                 max_arrivals: int, with_metrics: bool = False):
    """One server's round against a CALLER-SUPPLIED view of the global
    counters (``g_delta``/``g_rho``, [C] int64).  The healthy cluster
    passes the fresh psum (``_one_server_step``); the fault-injection
    layer (``robust.cluster``) passes a possibly stale held view -- the
    dmClock protocol tolerates stale counters by construction, which is
    exactly what makes delayed/lost piggyback updates injectable here
    without touching the tag algebra.

    Phase A: client c sends ``min(arrivals_per_client[c],
    max_arrivals)`` requests, each carrying view-derived ReqParams;
    arrivals interleave wave-major (every client's j-th request before
    any client's j+1-th, clients in slot order within a wave) -- the
    order the host-sim parity test replicates.
    Phase B: the engine makes ``decisions_per_step`` decisions.
    Phase C: completions fold into the tracker counters.
    """
    # the tracker STATE type picks the accounting policy
    borrowing = isinstance(tracker, BorrowTrackerState)
    prepare = borrow_tracker_prepare if borrowing else tracker_prepare

    c = arrivals_per_client.shape[0]
    slots = jnp.arange(c, dtype=jnp.int32)
    cost_c = jnp.broadcast_to(cost, (c,))   # per-client costs ([C] or
    #                                         scalar; heterogeneous
    #                                         multi-tenant rounds)
    for wave in range(max_arrivals):
        requesting = arrivals_per_client > wave
        # waves after a client's first request this round re-mark an
        # unchanged global counter, so their params are (0, 0) for
        # Orig / floor at (1, 1) for Borrowing -- the same streams the
        # host trackers emit for back-to-back requests with no
        # interleaved completions
        tracker, delta_out, rho_out = prepare(
            tracker, requesting, g_delta, g_rho)
        ops = kernels.IngestOps(
            kind=jnp.where(requesting, kernels.OP_ADD,
                           kernels.OP_NOP).astype(jnp.int32),
            slot=slots,
            time=jnp.broadcast_to(now, (c,)),
            cost=cost_c,
            rho=jnp.where(requesting, rho_out, 1),
            delta=jnp.where(requesting, delta_out, 1),
            resv_inv=jnp.zeros((c,), dtype=jnp.int64),
            weight_inv=jnp.zeros((c,), dtype=jnp.int64),
            limit_inv=jnp.zeros((c,), dtype=jnp.int64),
            order=jnp.zeros((c,), dtype=jnp.int64),
        )
        engine = kernels.ingest(engine, ops,
                                anticipation_ns=anticipation_ns)

    # --- scheduling decisions.  ``with_metrics`` (STATIC) rides the
    # obs vector in the same scan carry -- decisions bit-identical
    # either way (tests/test_obs.py) -- so the healthy path can merge
    # cluster totals in-graph (metrics_mesh_reduce) with no host-side
    # gather.
    if with_metrics:
        engine, now, decs, met = kernels.engine_run(
            engine, now, decisions_per_step,
            allow_limit_break=allow_limit_break,
            anticipation_ns=anticipation_ns, advance_now=True,
            with_metrics=True)
    else:
        engine, now, decs = kernels.engine_run(
            engine, now, decisions_per_step,
            allow_limit_break=allow_limit_break,
            anticipation_ns=anticipation_ns, advance_now=True)

    # --- completions -> counters (the response half of the protocol;
    # both policies fold completions identically)
    served = decs.type == kernels.RETURNING
    track = borrow_tracker_track if borrowing else tracker_track
    tracker = track(tracker, decs.slot, decs.cost, decs.phase, served)
    if with_metrics:
        return engine, tracker, now, decs, met
    return engine, tracker, now, decs


def _one_server_step(engine: EngineState, tracker: TrackerState,
                     now: jnp.ndarray, arrivals_per_client: jnp.ndarray,
                     cost: jnp.ndarray, decisions_per_step: int,
                     anticipation_ns: int, allow_limit_break: bool,
                     max_arrivals: int, with_metrics: bool = False):
    """One server's slice of a healthy cluster step (runs inside
    shard_map with a [1, ...]-shaped shard; vmapped over that unit
    axis): the distributed ReqParams come from the FRESH psum'd global
    counters, then the round runs via :func:`server_round`."""
    g_delta, g_rho = global_counters(
        tracker, lambda x: lax.psum(x, SERVER_AXIS))
    return server_round(
        engine, tracker, now, arrivals_per_client, cost, g_delta,
        g_rho, decisions_per_step=decisions_per_step,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break, max_arrivals=max_arrivals,
        with_metrics=with_metrics)


def cluster_step(cluster: ClusterState, arrivals: jnp.ndarray,
                 cost, mesh: Mesh, *,
                 decisions_per_step: int,
                 max_arrivals: int = 1,
                 anticipation_ns: int = 0,
                 allow_limit_break: bool = False,
                 advance_ns: int = 0,
                 with_metrics: bool = False,
                 with_pressure: bool = False):
    """Advance the whole cluster: ``arrivals`` is int32[S, C] request
    counts (honored up to the static ``max_arrivals`` per client per
    round, wave-major order -- see _one_server_step), sharded over
    servers.  ``cost`` is a scalar or an int64[C] per-client cost
    vector (heterogeneous multi-tenant rounds; reference requests carry
    per-request Cost, sim_recs.h:84).  Returns (cluster, decisions)
    with decisions' leaves [S, k]-shaped.

    Jit this (it is pure); under jit XLA turns the psum into one ICI
    all-reduce per step.

    ``advance_ns`` moves every server's virtual clock forward at round
    start (the real time elapsing between arrival waves; without it a
    weight-dominated cluster never advances past its reservation tags
    and the constraint phase never engages).

    ``with_metrics`` (STATIC) additionally returns ``(per_shard
    int64[S, NUM_METRICS], merged int64[NUM_METRICS])``: each server's
    obs vector from the same scan carry as its decisions, and the
    cluster total merged IN-GRAPH across the mesh (counter rows psum,
    hwm rows pmax -- ``obs.device.metrics_mesh_reduce``), so cluster
    totals need no host-side gather.  Decisions are bit-identical with
    the flag on or off (tests/test_obs.py pins the engine; the merged
    == host-summed pin lives in tests/test_cluster_realism.py).

    ``with_pressure`` (STATIC) additionally returns ``(per_shard
    int64[S, PRESS_FIELDS], merged int64[PRESS_FIELDS])``: each
    server's post-round scheduling-pressure vector (live eligible-set
    depth, backlog, peak, head-wait watermark --
    ``obs.provenance.pressure_vec``) and the cluster total through the
    same psum/pmax collective (``pressure_mesh_reduce``) -- the
    placement signal the ROADMAP rack-scheduling item routes on,
    published as ``dmclock_shard_pressure_*``
    (``obs.provenance.publish_shard_pressure``)."""
    from ..obs import provenance as obsprov

    cost = jnp.asarray(cost, dtype=jnp.int64)

    def shard_fn(engine, tracker, now, arr):
        step = functools.partial(
            _one_server_step,
            decisions_per_step=decisions_per_step,
            anticipation_ns=anticipation_ns,
            allow_limit_break=allow_limit_break,
            max_arrivals=max_arrivals, with_metrics=with_metrics)
        # shards carry a leading [1] server axis; vmap it away
        out = jax.vmap(
            lambda e, t, n, a: step(e, t, n, a, cost=cost),
        )(engine, tracker, now, arr)
        if with_metrics:
            engine, tracker, now, decs, met = out
            # local servers reduce with the vector's own merge
            # semantics, then one collective crosses the mesh; the
            # merged vector is replicated (P() out-spec)
            merged = obsdev.metrics_mesh_reduce(
                obsdev.metrics_combine_axis(met), SERVER_AXIS)
            out = (engine, tracker, now, decs, met, merged)
        if with_pressure:
            engine, tracker, now = out[0], out[1], out[2]
            press = jax.vmap(obsprov.pressure_vec)(engine, now)
            press_merged = obsprov.pressure_mesh_reduce(
                obsprov.pressure_combine_axis(press), SERVER_AXIS)
            out = out + (press, press_merged)
        return out

    spec = P(SERVER_AXIS)
    out_specs = (spec,) * 4
    if with_metrics:
        out_specs += (spec, P())
    if with_pressure:
        out_specs += (spec, P())
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=out_specs,
        check_vma=False)
    now0 = cluster.now + jnp.int64(advance_ns)
    out = fn(cluster.engine, cluster.tracker, now0, arrivals)
    engine, tracker, now, decs = out[:4]
    return (ClusterState(engine=engine, tracker=tracker, now=now),
            decs) + tuple(out[4:])


# Module-level jit cache for the healthy-path round driver (the
# engine/queue.py _JIT_CACHE convention): one compiled cluster_step
# program per (mesh, static-config) pair.
_ROUNDS_JIT_CACHE: dict = {}


def mesh_cache_key(mesh: Mesh, cfg: tuple) -> tuple:
    """THE cache key for every mesh-program module-jit cache
    (``mesh_step_jit``, :func:`jit_mesh_rounds`,
    ``parallel.mesh.jit_mesh_chunk``): (mesh, cfg) with the
    unhashable-mesh ``id()`` fallback some jax versions need.  One
    implementation so a jax-version fix lands in one place."""
    try:
        key = (mesh,) + cfg
        hash(key)
        return key
    except TypeError:            # unhashable mesh on some jax versions
        return (id(mesh),) + cfg


def mesh_step_jit(cache: dict, step_fn, mesh: Mesh, cfg: tuple):
    """Shared module-jit-cache helper for mesh step drivers (this
    module's healthy rounds and ``robust.cluster``'s faulty steps):
    one compiled ``jax.jit(partial(step_fn, mesh=mesh, <cfg>))`` per
    (mesh, static-config) pair.  ``cfg`` is the five-tuple
    (decisions_per_step, max_arrivals, anticipation_ns,
    allow_limit_break, advance_ns)."""
    from ..obs import compile_plane as _cplane

    key = mesh_cache_key(mesh, cfg)
    if key not in cache:
        (decisions_per_step, max_arrivals, anticipation_ns,
         allow_limit_break, advance_ns) = cfg
        # compile-plane-instrumented (obs.compile_plane): the mesh
        # step is the program the multichip item compiles per (mesh,
        # config) pair; entry is keyed WITHOUT the mesh repr (the
        # object id is meaningless across runs), but WITH the mesh
        # shape -- distinct meshes at one cfg are distinct programs,
        # and colliding them would record phantom retraces
        mesh_shape = tuple(np.shape(getattr(mesh, "devices", ())))
        cache[key] = _cplane.instrumented_jit(
            functools.partial(
                step_fn, mesh=mesh,
                decisions_per_step=decisions_per_step,
                max_arrivals=max_arrivals,
                anticipation_ns=anticipation_ns,
                allow_limit_break=allow_limit_break,
                advance_ns=advance_ns),
            cache=f"cluster.{getattr(step_fn, '__name__', 'step')}",
            entry=cfg + (mesh_shape,))
    return cache[key]


def run_cluster_rounds(cluster: ClusterState, arrivals_seq, cost,
                       mesh: Mesh, *, decisions_per_step: int,
                       max_arrivals: int = 1, anticipation_ns: int = 0,
                       allow_limit_break: bool = False,
                       advance_ns: int = 0, tracer=None):
    """Drive ``arrivals_seq.shape[0]`` healthy cluster steps from the
    host -- the happy-path twin of ``robust.cluster.run_with_plan``,
    so the tracing plane prices the mesh round-trip structure the same
    way on both paths.  ``tracer`` (``obs.spans.SpanTracer`` or None)
    records one ``cluster.round`` dispatch span per step (the whole
    shard_map launch) and a ``cluster.fetch`` span per decision
    readback; decisions are bit-identical with or without it.
    Returns ``(cluster, decs_seq)`` with per-step decisions fetched to
    host numpy."""
    from ..obs import spans as _spans

    step = mesh_step_jit(_ROUNDS_JIT_CACHE, cluster_step, mesh,
                         (decisions_per_step, max_arrivals,
                          anticipation_ns, allow_limit_break,
                          advance_ns))
    arrivals_seq = np.asarray(arrivals_seq)
    n_servers = cluster.now.shape[0]
    decs_seq = []
    for t in range(arrivals_seq.shape[0]):
        with _spans.span(tracer, "cluster.round", "dispatch",
                         step=t, servers=n_servers):
            cluster, decs = step(cluster,
                                 jnp.asarray(arrivals_seq[t]), cost)
        with _spans.span(tracer, "cluster.fetch", "fetch", step=t):
            decs_seq.append(jax.device_get(decs))
    return cluster, decs_seq


# ----------------------------------------------------------------------
# mesh serving plane: fused multi-round programs with batched
# delta/rho exchange (docs/ENGINE.md "Mesh serving")
# ----------------------------------------------------------------------

class MeshRounds(NamedTuple):
    """One fused mesh launch's outputs (``run_mesh_rounds``).

    ``decs`` leaves are ``[S, E, k]`` (server, round, decision slot);
    slice round ``t`` with :func:`mesh_decs_seq` to recover the
    per-step ``[S, k]`` stream the host-loop drivers emit.  ``metrics``
    is the per-shard ``int64[S, NUM_METRICS]`` vector accumulated
    across all E rounds with the robust path's delta accounting, so a
    zero-fault host loop and a mesh launch produce the same totals."""

    cluster: ClusterState
    view_delta: jnp.ndarray   # int64[S, C] held counter views
    view_rho: jnp.ndarray     # int64[S, C]
    metrics: jnp.ndarray      # int64[S, NUM_METRICS]
    decs: object              # kernels.Decision, [S, E, k] leaves
    merged: object = None     # int64[NUM_METRICS] (with_merged)
    pressure: object = None   # int64[S, PRESS_FIELDS] (with_pressure)
    pressure_merged: object = None


def round_sync_mask(epochs: int, counter_sync_every: int,
                    round0: int = 0) -> np.ndarray:
    """The GLOBAL counter-sync grid as a host bool mask over one
    launch's rounds: round ``round0 + t`` syncs iff it lies on the
    ``counter_sync_every`` grid.  One implementation shared by the
    healthy fused rounds (:func:`run_mesh_rounds`) and the chaos
    fused rounds (``robust.cluster.run_mesh_rounds_with_plan``), so
    the two programs cannot disagree about where a chunked launch
    sits on the grid."""
    every = max(int(counter_sync_every), 1)
    return (int(round0) + np.arange(int(epochs))) % every == 0


def init_mesh_views(n_servers: int, n_clients: int):
    """Held counter views at the protocol origin (counters start at 1,
    ``dmclock_client.h:191-198``) -- the same origin ``robust.cluster.
    init_robust`` gives its view arrays, so a mesh launch and the
    host-loop degraded path start from identical state."""
    return (jnp.ones((n_servers, n_clients), dtype=jnp.int64),
            jnp.ones((n_servers, n_clients), dtype=jnp.int64))


def _mesh_round_body(engine, tracker, now, arr, vd, vr, met, sync, *,
                     cost, decisions_per_step, anticipation_ns,
                     allow_limit_break, max_arrivals, advance_ns):
    """One fused round (inside the per-server scan): refresh the held
    counter view from the mesh psum on sync rounds only (the
    ``counter_sync_every`` staleness knob -- the paper's piggybacked
    views are naturally stale, and ``server_round`` takes the view as
    an argument precisely so a stale one is protocol-safe), then run
    the round and fold the completion metrics with the degraded path's
    delta accounting (``robust.cluster._one_server_step_faulty``'s
    zero-fault arm), so mesh and host-loop totals are comparable."""
    g_d, g_r = global_counters(
        tracker, lambda x: lax.psum(x, SERVER_AXIS))
    vd = jnp.where(sync, g_d, vd)
    vr = jnp.where(sync, g_r, vr)
    engine, tracker, now, decs = server_round(
        engine, tracker, now + advance_ns, arr, cost, vd, vr,
        decisions_per_step=decisions_per_step,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        max_arrivals=max_arrivals)
    served = decs.type == kernels.RETURNING
    n_served = jnp.sum(served).astype(jnp.int64)
    n_resv = jnp.sum(served & (decs.phase == 0)).astype(jnp.int64)
    met = obsdev.metrics_combine(met, obsdev.metrics_delta(
        decisions=n_served, resv=n_resv, prop=n_served - n_resv,
        limit_break=jnp.sum(decs.limit_break).astype(jnp.int64),
        ring_hwm=jnp.max(engine.depth).astype(jnp.int64)))
    return engine, tracker, now, vd, vr, met, decs


def run_mesh_rounds(cluster: ClusterState, arrivals_seq, cost,
                    mesh: Mesh, *, decisions_per_step: int,
                    max_arrivals: int = 1, anticipation_ns: int = 0,
                    allow_limit_break: bool = False,
                    advance_ns: int = 0,
                    counter_sync_every: int = 1, round0: int = 0,
                    view_delta=None, view_rho=None, metrics=None,
                    with_merged: bool = False,
                    with_pressure: bool = False) -> MeshRounds:
    """The mesh serving plane's cluster program: ONE ``shard_map``
    launch advances every server by ``E = arrivals_seq.shape[0]``
    whole rounds (a ``lax.scan`` over rounds inside each shard), with
    the [C]-sized delta/rho counter psum -- the paper's piggyback
    protocol, batched -- exchanged once per round boundary instead of
    once per decision batch, and only on rounds where
    ``t % counter_sync_every == 0`` (round 0 always syncs; between
    syncs every server serves from its HELD view, exactly the
    stale-counter tolerance ``robust.cluster`` injects as the
    ``delay_counters`` fault -- the K>1 digest gate in
    ``tests/test_cluster_realism.py`` pins the two paths equal).

    ``arrivals_seq`` is int32[E, S, C] in round order.  With K=1 the
    launch is decision-for-decision AND counter-view-for-counter-view
    identical to ``E`` host-driven ``robust_cluster_step``s under a
    zero-fault plan; the only difference is launches: 1 vs 3E host
    round-trips.  ``view_delta``/``view_rho``/``metrics`` resume held
    state across launches (``None`` = the protocol origin / zeros)
    and ``round0`` anchors this launch on the GLOBAL round grid --
    the sync mask is ``(round0 + t) % K == 0`` -- so chunked mesh
    launches compose exactly like the host loop at ANY K (pass the
    previous launch's end round; the composition test pins K=2).

    ``with_merged`` additionally mesh-reduces the per-shard metric
    vectors in-graph (psum counters / pmax hwm); ``with_pressure``
    returns the post-run per-shard pressure gauges + their merged
    total (``obs.provenance``), replicated."""
    from ..obs import provenance as obsprov

    arrivals_seq = jnp.asarray(arrivals_seq, dtype=jnp.int32)
    epochs = int(arrivals_seq.shape[0])
    n_servers = cluster.now.shape[0]
    n_clients = arrivals_seq.shape[2]
    cost = jnp.asarray(cost, dtype=jnp.int64)
    sync_mask = jnp.asarray(
        round_sync_mask(epochs, counter_sync_every, round0))
    if view_delta is None or view_rho is None:
        view_delta, view_rho = init_mesh_views(n_servers, n_clients)
    if metrics is None:
        metrics = jnp.zeros((n_servers, obsdev.NUM_METRICS),
                            dtype=jnp.int64)
    # [E, S, C] -> [S, E, C]: the shard axis must lead for P(servers)
    arr_s = jnp.swapaxes(arrivals_seq, 0, 1)

    def per_server(engine, tracker, now, arrs, vd, vr, met):
        def body(carry, xs):
            engine, tracker, now, vd, vr, met = carry
            arr, sync = xs
            engine, tracker, now, vd, vr, met, decs = \
                _mesh_round_body(
                    engine, tracker, now, arr, vd, vr, met, sync,
                    cost=cost, decisions_per_step=decisions_per_step,
                    anticipation_ns=anticipation_ns,
                    allow_limit_break=allow_limit_break,
                    max_arrivals=max_arrivals, advance_ns=advance_ns)
            return (engine, tracker, now, vd, vr, met), decs

        (engine, tracker, now, vd, vr, met), decs = lax.scan(
            body, (engine, tracker, now, vd, vr, met),
            (arrs, sync_mask))
        return engine, tracker, now, vd, vr, met, decs

    def shard_fn(engine, tracker, now, arrs, vd, vr, met):
        out = jax.vmap(per_server)(engine, tracker, now, arrs, vd,
                                   vr, met)
        if with_merged:
            out = out + (obsdev.metrics_mesh_reduce(
                obsdev.metrics_combine_axis(out[5]), SERVER_AXIS),)
        if with_pressure:
            press = jax.vmap(obsprov.pressure_vec)(out[0], out[2])
            out = out + (press, obsprov.pressure_mesh_reduce(
                obsprov.pressure_combine_axis(press), SERVER_AXIS))
        return out

    spec = P(SERVER_AXIS)
    out_specs = (spec,) * 7
    if with_merged:
        out_specs += (P(),)
    if with_pressure:
        out_specs += (spec, P())
    fn = jax.shard_map(shard_fn, mesh=mesh,
                   in_specs=(spec,) * 7, out_specs=out_specs,
                   check_vma=False)
    outs = fn(cluster.engine, cluster.tracker, cluster.now, arr_s,
              view_delta, view_rho, metrics)
    engine, tracker, now, vd, vr, met, decs = outs[:7]
    rest = list(outs[7:])
    merged = rest.pop(0) if with_merged else None
    press, press_merged = (rest if with_pressure else (None, None))
    return MeshRounds(
        cluster=ClusterState(engine=engine, tracker=tracker, now=now),
        view_delta=vd, view_rho=vr, metrics=met, decs=decs,
        merged=merged, pressure=press, pressure_merged=press_merged)


_MESH_ROUNDS_JIT_CACHE: dict = {}


def jit_mesh_rounds(mesh: Mesh, *, epochs: int,
                    decisions_per_step: int, max_arrivals: int = 1,
                    anticipation_ns: int = 0,
                    allow_limit_break: bool = False,
                    advance_ns: int = 0, counter_sync_every: int = 1,
                    round0: int = 0, with_merged: bool = False,
                    with_pressure: bool = False):
    """Module-cached jit of :func:`run_mesh_rounds` for one (mesh,
    static-config) pair -- ``(cluster, arrivals_seq, cost, view_d,
    view_r, metrics) -> MeshRounds``.  The fused multi-round program
    is the mesh plane's expensive compile; the entry is keyed with the
    mesh SHAPE (not its repr) like ``mesh_step_jit``.  ``round0``
    anchors the sync grid (static; distinct chunk positions at K>1
    are distinct programs -- at K=1 every position shares one)."""
    from ..obs import compile_plane as _cplane

    cfg = (epochs, decisions_per_step, max_arrivals, anticipation_ns,
           allow_limit_break, advance_ns, counter_sync_every,
           int(round0) % max(int(counter_sync_every), 1),
           with_merged, with_pressure)
    key = mesh_cache_key(mesh, cfg)
    if key not in _MESH_ROUNDS_JIT_CACHE:
        def run(cluster, arrivals_seq, cost, view_d, view_r, met):
            return run_mesh_rounds(
                cluster, arrivals_seq, cost, mesh,
                decisions_per_step=decisions_per_step,
                max_arrivals=max_arrivals,
                anticipation_ns=anticipation_ns,
                allow_limit_break=allow_limit_break,
                advance_ns=advance_ns,
                counter_sync_every=counter_sync_every,
                round0=round0,
                view_delta=view_d, view_rho=view_r, metrics=met,
                with_merged=with_merged, with_pressure=with_pressure)

        mesh_shape = tuple(np.shape(getattr(mesh, "devices", ())))
        _MESH_ROUNDS_JIT_CACHE[key] = _cplane.instrumented_jit(
            run, cache="cluster.mesh_rounds",
            entry=cfg + (mesh_shape,))
    return _MESH_ROUNDS_JIT_CACHE[key]


def mesh_decs_seq(decs) -> list:
    """Re-slice a fused launch's ``[S, E, k]`` decision leaves into
    the per-round ``[S, k]`` stream the host-loop drivers produce
    (``robust.cluster.run_with_plan``), so ``decision_digest`` applies
    to both unchanged."""
    epochs = int(np.asarray(decs.type).shape[1])
    host = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), decs)
    return [jax.tree.map(lambda a: a[:, t], host)
            for t in range(epochs)]


def create_clients(cluster: ClusterState, new_mask: jnp.ndarray,
                   resv_inv: jnp.ndarray, weight_inv: jnp.ndarray,
                   limit_inv: jnp.ndarray, mesh: Mesh) -> ClusterState:
    """Mid-run client creation, cluster-wide (the reference admits new
    clients at their first request, dmclock_server.h:920-932; here
    creation is an explicit sharded OP_CREATE ingest so slot==client
    stays a cluster invariant).

    ``new_mask`` bool[C] picks the slots to install; the QoS inverse
    arrays are [C] (only masked entries are read).  Creation order =
    slot index, preserving the cluster-wide deterministic tie-break.
    New clients join every server; their tracker counters start fresh.
    """
    c = new_mask.shape[0]
    slots = jnp.arange(c, dtype=jnp.int32)
    ops = kernels.IngestOps(
        kind=jnp.where(new_mask, kernels.OP_CREATE,
                       kernels.OP_NOP).astype(jnp.int32),
        slot=slots,
        time=jnp.zeros((c,), dtype=jnp.int64),
        cost=jnp.ones((c,), dtype=jnp.int64),
        rho=jnp.ones((c,), dtype=jnp.int64),
        delta=jnp.ones((c,), dtype=jnp.int64),
        resv_inv=resv_inv, weight_inv=weight_inv, limit_inv=limit_inv,
        order=slots.astype(jnp.int64),
    )

    def shard_fn(engine):
        return jax.vmap(lambda e: kernels.ingest(
            e, ops, anticipation_ns=0))(engine)

    spec = P(SERVER_AXIS)
    engine = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False)(cluster.engine)
    return cluster._replace(engine=engine)
