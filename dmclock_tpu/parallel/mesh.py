"""Mesh serving plane: shard_map'd full per-device epoch engines.

The paper's distributed story -- many servers each running a complete
mClock queue, coordinated only by piggybacked per-client delta/rho
counters -- as one TPU mesh program.  Each shard owns a full
client-state pytree + rings (the ``parallel.cluster`` stacked layout)
and runs the COMPLETE fused epoch program (the PR-8 stream-chunk body:
on-device admission clamp + superwave ingest + one full epoch of any
of the three engines, telemetry riding the carry) for a whole chunk of
epochs inside ONE mesh launch.  The only cross-shard traffic is the
[C]-sized counter-view psum -- the paper's per-request four-scalar
piggyback contract, batched to epoch boundaries -- refreshed on epochs
where ``epoch % counter_sync_every == 0`` (the staleness knob: the
protocol tolerates stale views by construction, which is what makes
K>1 safe; ``parallel.cluster.run_mesh_rounds`` pins the same knob
decision-exact against the host-loop ``delay_counters`` fault).

Model: each shard is one SERVER owning a DISTINCT ``n``-client
partition of the deployment's population -- ``S * n`` client
contracts live across the mesh, each with its own queue state and
arrival stream (what makes ``obs.capacity.plan_capacity``'s per-shard
HBM inversion the shard-count planner: more clients -> more shards).
The partitions share one contract LAYOUT (slot i carries the same QoS
triple on every shard), so the initial per-shard states are
numerically identical and only the independent arrival streams
diverge them.  Aggregate throughput is the sum of per-shard decision
streams.  The counter plane exchanges the [n]-sized per-slot
delta/rho psum at epoch boundaries: the piggyback protocol's wire
shape and cadence, measured for real; under partitioning the psum'd
view aggregates the S like-contracted clients sharing a slot index
(at S=1 it degenerates to the exact single-server counters, and the
REPLICATED-population model -- where the view IS client i's global
counter feeding its ReqParams -- is the ``parallel.cluster``
``run_mesh_rounds`` program, digest-pinned against the host loop).
Counters count unit-cost completions (the job's superwave is
unit-cost), folded per epoch from the SLO window block's exact
per-client delivered columns -- threaded scatter-free through all
three engines since PR-10 -- so the fold cannot perturb a decision.

Layering (the ``engine.stream`` convention): this module owns the pure
device program + host helpers; ``robust.guarded.run_mesh_chunk_guarded``
adds retry + the guard-trip fallback; ``robust.supervisor`` drives
chunks between checkpoint boundaries as ``EpochJob(engine_loop="mesh",
n_shards=S)``; ``bench.py --mode mesh`` runs the aggregate-throughput
trajectory.  S=1 is bit-identical to the single-engine stream loop BY
CONSTRUCTION: both trace ``engine.stream.make_epoch_step``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import fastpath
from ..engine import stream as stream_mod
from ..obs import slo as obsslo
from .cluster import SERVER_AXIS, make_mesh  # noqa: F401 (re-export)
from .tracker import global_counters_from


class MeshChunk(NamedTuple):
    """One fused mesh chunk's device outputs.

    ``outs`` holds the engine's stacked per-epoch fields with a
    leading ``[S, E]`` (shard, epoch) axis pair; ``cd``/``cr`` are the
    per-shard per-client completion counters (``int64[S, N]``, the
    psum source), ``view_d``/``view_r`` the held counter views after
    the chunk.  ``slo_merged`` is the cluster-wide window block merged
    IN-GRAPH across the mesh via ``obs.slo.window_mesh_reduce``
    (replicated; ``int64[N, W_FIELDS]``) -- the one conformance table
    the SLO plane rolls.  ``flight`` is the stacked per-shard HBM
    flight-ring state (``with_flight`` chunks; each shard records its
    own commits, the host merges rings in shard order at drain)."""

    state: object             # stacked EngineState, [S, ...] leaves
    outs: dict                # [S, E, ...] stacked engine fields
    cd: jnp.ndarray           # int64[S, N] completions (delta source)
    cr: jnp.ndarray           # int64[S, N] resv-phase completions
    view_d: jnp.ndarray       # int64[S, N] held global-delta views
    view_r: jnp.ndarray       # int64[S, N]
    hists: object = None      # stacked telemetry accumulators
    ledger: object = None
    slo: object = None        # int64[S, N, W_FIELDS] per-shard blocks
    prov: object = None
    slo_merged: object = None  # int64[N, W_FIELDS] (window_mesh_reduce)
    flight: object = None     # stacked obs.flight.FlightState [S, ...]


def stack_shards(tree, n_shards: int, mesh: Optional[Mesh] = None):
    """Broadcast a single-engine pytree to the stacked ``[S, ...]``
    per-shard layout: every shard's DISTINCT client partition starts
    from the identical contract layout/state (independent arrival
    streams supply the divergence), optionally placing each leaf
    split over the ``servers`` mesh axis."""
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_shards,) + jnp.shape(a)),
        tree)
    if mesh is not None:
        sharding = NamedSharding(mesh, P(SERVER_AXIS))
        stacked = jax.tree.map(
            lambda a: jax.device_put(a, sharding), stacked)
    return stacked


def unstack_shard(tree, s: int = 0):
    """Slice shard ``s`` back out of a stacked pytree (the S=1
    canonicalization: a 1-shard mesh IS a single engine, and the
    identity gate compares it against the round/stream loops)."""
    return jax.tree.map(lambda a: a[s], tree)


def counter_init(n_shards: int, n: int):
    """Fresh counter plane: zero per-shard completions, views at the
    protocol's counters-start-at-1 origin (``dmclock_client.h``)."""
    z = jnp.zeros((n_shards, n), dtype=jnp.int64)
    one = jnp.ones((n_shards, n), dtype=jnp.int64)
    return z, z, one, one


def mask_epoch_outs(outs: dict, up, fault_vec):
    """Mask one DOWN epoch's engine outputs to their committed-nothing
    neutrals (the ``robust.cluster`` decision-slots-read-NONE
    semantics, field-typed for the stream-chunk layout): guard vectors
    read True (nothing ran, nothing tripped), slots read -1, every
    count/cost/class reads 0.  ``metrics`` is zeroed and replaced by
    the epoch's fault-event delta (``fault_vec``; also added on LIVE
    epochs, where the engine metrics are kept).  The host chaos
    replay (``robust.guarded``) builds byte-identical rows from the
    same table -- one implementation would need shapes the host does
    not have, so the NAME table here is the shared contract."""
    masked = {}
    for name, arr in outs.items():
        if name == "metrics":
            masked[name] = jnp.where(up, arr, 0) + fault_vec
        elif name in ("guards_ok", "progress_ok"):
            masked[name] = jnp.where(up, arr, jnp.ones_like(arr))
        elif name == "slot":
            masked[name] = jnp.where(up, arr, jnp.full_like(arr, -1))
        else:
            masked[name] = jnp.where(up, arr, jnp.zeros_like(arr))
    return masked


def build_mesh_chunk(mesh: Mesh, *, engine: str, epochs: int, m: int,
                     k: int = 0, chain_depth: int = 4,
                     dt_epoch_ns: int, waves: int,
                     anticipation_ns: int = 0,
                     allow_limit_break: bool = False,
                     with_metrics: bool = True,
                     select_impl: str = "sort", tag_width: int = 64,
                     window_m: Optional[int] = None,
                     calendar_impl: str = "minstop",
                     ladder_levels: int = 8,
                     wheel_kernel: str = "xla",
                     counter_sync_every: int = 1,
                     collective_skipping: Optional[bool] = None,
                     ingest: bool = True,
                     with_faults: bool = False,
                     with_flight: bool = False,
                     with_pressure: bool = False):
    """Build the pure mesh chunk program ``(state, cd, cr, view_d,
    view_r, epoch0, counts, hists, ledger, slo, prov, flight, faults)
    -> MeshChunk`` for one static configuration.

    ``counts`` is ``int32[S, E, N]`` of RAW per-shard Poisson draws
    (shard axis leading so ``P(servers)`` splits it); ``epoch0`` is a
    TRACED int64 scalar, and the counter-sync mask is computed
    IN-GRAPH from the global epoch index ``(epoch0 + i) %
    counter_sync_every == 0``, so one compiled program serves every
    chunk position and the sync grid is global, not per-chunk.  ``slo``
    must always be a window block (``int64[S, N, W_FIELDS]``): the
    counter plane diffs its delivered columns per epoch -- when the
    job runs with the SLO plane off the caller passes a throwaway
    zero block.

    ``with_faults`` compiles the PR-3 fault model INTO the chunk:
    ``faults`` is a ``robust.faults.FaultChunk``-shaped 5-tuple of
    traced per-shard arrays (``up``/``skew_ns``/``delay_counters``/
    ``dup_completions`` [S, E] + ``up_prev`` [S]) precomputed on the
    host from the plan oracle.  Per epoch, per shard:

    - a DOWN shard commits nothing -- engine state, telemetry
      accumulators, and the SLO window block all keep their entry
      values, its decision outputs read the neutral masks
      (:func:`mask_epoch_outs`), and its frozen ``cd``/``cr``
      contribution keeps the counter psum MONOTONE (exactly the
      ``robust.cluster`` degraded-path semantics);
    - a live shard's view refreshes from the psum only on the global
      sync grid AND when its piggyback updates are not delayed; a
      RESTART (down -> up transition) always re-syncs -- the in-graph
      twin of ``resync_tracker``'s re-marking;
    - ``dup_completions`` folds the epoch's completion delta into the
      counters TWICE (the at-least-once response-network failure);
    - ``skew_ns`` lenses the shard's epoch clock (ingest + serve see
      ``t + skew``; the index-derived clock makes it per-epoch, not
      cumulative);
    - every injected event lands in the epoch's metrics vector rows
      (``server_dropouts``/``tracker_resyncs``/``faults_injected``),
      summing to the ``plan_events`` oracle exactly.

    An all-benign fault tuple (``zero_plan`` sliced) is value-
    identical to ``with_faults=False`` -- the zero-fault gate in
    ``scripts/ci.sh``.

    ``collective_skipping`` (STATIC) restructures the epoch scan into
    ``epochs // counter_sync_every``-sized SYNC GROUPS: the delta/rho
    psum executes ONCE at each group head and the non-sync epochs run
    COLLECTIVE-FREE -- zero all-reduces in the compiled HLO (the
    tests/test_mesh.py cost-analysis gate), where the flat scan
    executed the psum every epoch and K only gated the view refresh.
    Bit-identical to the flat scan when ``epoch0`` lands on the sync
    grid (``epoch0 % counter_sync_every == 0``): the group head IS
    the one on-grid epoch of its group, and its psum reads the same
    entry counters the flat program read there.  Off-grid chunks keep
    the flat program (the guarded runner picks per chunk).  Default
    ``None`` auto-enables for fault-free chunks with ``epochs``
    divisible by K > 1; faulty chunks always run flat -- a mid-group
    restart must re-sync from a FRESH psum, which is exactly the
    collective the skipping removes.

    ``with_pressure`` threads the mid-epoch pressure probe
    (``engine.stream.make_epoch_step``) through the chunk:
    ``outs["pressure"]`` stacks to ``int64[S, E, PRESS_FIELDS]``, a
    down epoch's row masks to zeros (a nonneg no-op under the peak
    max), and the probe is shard-local -- no collective, so the
    collective-skipping cost gates are unaffected."""
    from ..obs import device as obsdev

    assert engine in fastpath.EPOCH_ENGINES, engine
    epochs = int(epochs)
    assert epochs >= 1, "a mesh chunk needs at least one epoch"
    kw = fastpath.epoch_scan_kwargs(
        engine, k=k, chain_depth=chain_depth, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics)
    dt = int(dt_epoch_ns)
    every = max(int(counter_sync_every), 1)
    if collective_skipping is None:
        collective_skipping = (not with_faults and every > 1
                               and epochs % every == 0)
    if collective_skipping:
        assert not with_faults, \
            "collective skipping needs the fault-free chunk (a " \
            "mid-group restart must re-sync from a fresh psum)"
        assert epochs % every == 0, \
            f"collective skipping needs epochs ({epochs}) divisible " \
            f"by counter_sync_every ({every})"
    epoch_step = stream_mod.make_epoch_step(
        engine=engine, m=m, kw=kw, dt_epoch_ns=dt, waves=waves,
        ingest=ingest, with_pressure=with_pressure)

    def per_server(st, cd, cr, vd, vr, epoch0, counts_s, h, l, s, p,
                   f, flt):
        def body(carry, xs, counters=None):
            st, cd, cr, vd, vr, h, l, s, p, f, up_prev = carry
            if with_faults:
                counts_e, i, up, skew, delay, dup = xs
            else:
                counts_e, i = xs
                up = up_prev        # the all-up constant
                skew = jnp.int64(0)
            # batched delta/rho exchange at the epoch boundary: the
            # views refresh from the mesh psum only on the global
            # sync grid; between syncs every shard serves from its
            # held (stale) view -- the paper's tolerance, as data.
            # The collective runs on EVERY shard (SPMD); a down
            # shard's counters are frozen, so the psum stays monotone.
            # Under collective skipping the GROUP-HEAD psum arrives in
            # ``counters`` instead -- on an aligned chunk the head is
            # the only epoch that reads it, and it read the same
            # values here
            if counters is None:
                g_d, g_r = global_counters_from(
                    cd, cr, lambda x: lax.psum(x, SERVER_AXIS))
            else:
                g_d, g_r = counters
            sync = ((epoch0 + i) % every) == 0
            if with_faults:
                restart = up & ~up_prev
                dropout = ~up & up_prev
                # live non-delayed shards refresh on the grid; a
                # restart always re-syncs (resync_tracker's twin); a
                # down shard holds its frozen view
                refresh = (sync & up & ~delay) | restart
            else:
                refresh = sync
            vd = jnp.where(refresh, g_d, vd)
            vr = jnp.where(refresh, g_r, vr)
            t_base = (epoch0 + i) * dt + skew
            (st2, h2, l2, f2, s2, p2), outs = epoch_step(
                st, t_base, counts_e, h, l, f, s, p)
            if with_faults:
                # commit gate: a down shard keeps last-good state --
                # engine, telemetry, flight ring, SLO block alike --
                # and its outputs read the neutral masks
                def keep(new, old):
                    return None if new is None else jax.tree.map(
                        lambda a, b: jnp.where(up, a, b), new, old)

                st2, h2, l2, f2, p2 = (keep(st2, st), keep(h2, h),
                                       keep(l2, l), keep(f2, f),
                                       keep(p2, p))
                s2 = jnp.where(up, s2, s)
                perturb = ((dup & up).astype(jnp.int64)
                           + (delay & up).astype(jnp.int64)
                           + ((skew != 0) & up).astype(jnp.int64))
                events = (dropout.astype(jnp.int64)
                          + restart.astype(jnp.int64))
                outs = mask_epoch_outs(outs, up, obsdev.metrics_delta(
                    server_dropouts=dropout.astype(jnp.int64),
                    tracker_resyncs=restart.astype(jnp.int64),
                    faults_injected=events + perturb))
            # completions -> counters: the window block's delivered
            # columns are exact per-client counts (PR-10), so the
            # per-epoch diff IS this epoch's completion fold -- no
            # scatter, no second accumulator, no decision perturbed
            d_ops = s2[:, obsslo.W_OPS] - s[:, obsslo.W_OPS]
            d_resv = (s2[:, obsslo.W_RESV_OPS]
                      - s[:, obsslo.W_RESV_OPS])
            if with_faults:
                # duplicated completions: this epoch's batch folds
                # into the counters twice (masked; +0 is exact)
                mult = 1 + (dup & up).astype(jnp.int64)
                d_ops = d_ops * mult
                d_resv = d_resv * mult
            cd = cd + d_ops
            cr = cr + d_resv
            return (st2, cd, cr, vd, vr, h2, l2, s2, p2, f2,
                    up if with_faults else up_prev), outs

        idx = jnp.arange(epochs, dtype=jnp.int64)
        if not ingest:
            counts_s = jnp.zeros((epochs, 0), dtype=jnp.int32)
        if with_faults:
            up_s, skew_s, delay_s, dup_s, up0 = flt
            xs = (counts_s, idx, up_s, skew_s, delay_s, dup_s)
        else:
            up0 = jnp.asarray(True)
            xs = (counts_s, idx)
        carry0 = (st, cd, cr, vd, vr, h, l, s, p, f, up0)
        if collective_skipping:
            # sync groups: ONE psum per group of ``every`` epochs,
            # computed at the group head from the carried counters,
            # and the inner scan runs collective-free.  On an aligned
            # chunk the head is the group's only on-grid epoch, so
            # the refresh mask inside ``body`` consumes exactly the
            # values the flat program's per-epoch psum produced there
            # (off-grid epochs never read ``g_d``/``g_r`` at all)
            groups = epochs // every
            gxs = jax.tree.map(
                lambda a: a.reshape((groups, every) + a.shape[1:]),
                xs)

            def group(carry, xs_g):
                counters = global_counters_from(
                    carry[1], carry[2],
                    lambda x: lax.psum(x, SERVER_AXIS))
                return lax.scan(
                    lambda c, x: body(c, x, counters=counters),
                    carry, xs_g)

            carry, outs = lax.scan(group, carry0, gxs)
            outs = jax.tree.map(
                lambda a: a.reshape((epochs,) + a.shape[2:]), outs)
        else:
            carry, outs = lax.scan(body, carry0, xs)
        st, cd, cr, vd, vr, h, l, s, p, f = carry[:10]
        return st, cd, cr, vd, vr, h, l, f, s, p, outs

    def shard_fn(state, cd, cr, vd, vr, epoch0, counts,
                 hists, ledger, slo, prov, flight, faults):
        out = jax.vmap(
            per_server,
            in_axes=(0, 0, 0, 0, 0, None, 0, 0, 0, 0, 0, 0, 0),
        )(state, cd, cr, vd, vr, epoch0, counts, hists, ledger, slo,
          prov, flight, faults)
        # cluster-wide conformance: local combine over this shard's
        # vmapped servers, then ONE collective across the mesh --
        # counter columns psum, the contract-epoch column pmax
        # (obs.slo.window_mesh_reduce); replicated out-spec
        merged = obsslo.window_mesh_reduce(
            obsslo.window_combine_axis(out[8]), SERVER_AXIS)
        return out + (merged,)

    spec = P(SERVER_AXIS)
    in_specs = (spec,) * 5 + (P(),) + (spec,) * 7
    out_specs = (spec,) * 11 + (P(),)

    def chunk(state, cd, cr, vd, vr, epoch0, counts, hists=None,
              ledger=None, slo=None, prov=None, flight=None,
              faults=None) -> MeshChunk:
        epoch0 = jnp.asarray(epoch0, dtype=jnp.int64)
        if with_faults:
            assert faults is not None, \
                "with_faults=True needs the FaultChunk arrays"
            faults = (jnp.asarray(faults[0], dtype=bool),
                      jnp.asarray(faults[1], dtype=jnp.int64),
                      jnp.asarray(faults[2], dtype=bool),
                      jnp.asarray(faults[3], dtype=bool),
                      jnp.asarray(faults[4], dtype=bool))
        else:
            faults = None
        fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        (state, cd, cr, vd, vr, hists, ledger, flight, slo, prov,
         outs, merged) = fn(state, cd, cr, vd, vr, epoch0, counts,
                            hists, ledger, slo, prov, flight, faults)
        return MeshChunk(state=state, outs=outs, cd=cd, cr=cr,
                         view_d=vd, view_r=vr, hists=hists,
                         ledger=ledger, slo=slo, prov=prov,
                         slo_merged=merged, flight=flight)

    return chunk


# module-level jit cache keyed by the full static configuration + the
# mesh SHAPE (the mesh_step_jit convention: the object id is
# meaningless across runs, but distinct meshes at one cfg are distinct
# programs and colliding them would record phantom retraces)
_MESH_CHUNK_JIT_CACHE: dict = {}


def jit_mesh_chunk(mesh: Mesh, **cfg):
    from ..obs import compile_plane as _cplane

    from .cluster import mesh_cache_key

    mesh_shape = tuple(np.shape(getattr(mesh, "devices", ())))
    key = (mesh_shape,) + tuple(sorted(cfg.items()))
    full_key = mesh_cache_key(mesh, key)
    if full_key not in _MESH_CHUNK_JIT_CACHE:
        fn = build_mesh_chunk(mesh, **cfg)
        _MESH_CHUNK_JIT_CACHE[full_key] = _cplane.instrumented_jit(
            fn, cache="mesh.chunk", entry=key)
    return _MESH_CHUNK_JIT_CACHE[full_key]


def shard_epoch_view(engine: str, outs: dict, s: int, i: int):
    """Reconstruct shard ``s``'s epoch ``i`` result object from the
    fetched ``[S, E, ...]`` stacked outputs -- the stream loop's
    ``epoch_view`` over one shard's slice, so the supervisor's chain
    digest sees byte-identical arrays at S=1."""
    return stream_mod.epoch_view(
        engine, {name: arr[s] for name, arr in outs.items()}, i)


def mesh_epoch_results(engine: str, outs: dict, i: int) -> tuple:
    """Epoch ``i``'s digest-ready result rows: one PER-SHARD tuple of
    result views in shard order (flatten for the chain digest -- the
    flat order is unchanged from before the grouping; the per-shard
    structure is what lets a churn job canonicalize each shard's
    results through that shard's own slot map).  At S=1 the flattened
    row is exactly the stream loop's tuple."""
    n_shards = next(iter(outs.values())).shape[0]
    return tuple((shard_epoch_view(engine, outs, s, i),)
                 for s in range(n_shards))


def mesh_epoch_decisions(engine: str, outs: dict, i: int) -> int:
    """Decisions epoch ``i`` committed across ALL shards (the
    aggregate-throughput numerator)."""
    return int(np.asarray(outs["count"][:, i]).sum())
