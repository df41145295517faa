"""Crash-equivalent supervised epoch runs (docs/ROBUSTNESS.md).

PR-3 made device-level faults injectable; the host process stayed a
single point of failure.  This module closes that gap the way
RackSched survives per-server failures through stateless re-dispatch
(PAPERS.md): the epoch loop becomes a **resumable job** under a
supervisor --

- the job runs epochs of any of the three epoch engines through the
  guarded-commit contract (``robust.guarded.run_epoch_guarded``),
  ingesting Poisson arrivals drawn from a checkpointed host RNG;
- at epoch boundaries it writes **rotating crash-safe checkpoints**
  (``utils.checkpoint.save_pytree_rotating``) of the FULL run state:
  engine pytree, obs metrics vector, RNG bit-generator state, the
  decision-stream chain digest, the epoch/decision counters, and the
  degradation-ladder position;
- the supervisor (child process via spawn, or an in-process
  trampoline for tests) restarts a killed job with bounded
  exponential backoff; resume lands on the **newest intact** rotation
  snapshot (``restore_pytree_rotating``'s fallback walk) and replays
  forward deterministically.

The headline invariant is the **crash-equivalence digest gate**: a
run SIGKILLed at ANY :class:`~.host_faults.HostFaultPlan` point and
resumed produces the same decision-stream digest, the same final
engine state, and the same metric totals -- modulo the ``resume_*``
rows (``obs.device.RESUME_ROWS``) -- as the uninterrupted run.
Exactly-once is by construction: the digest is a sha256 **chain**
carried inside the checkpoint, so decisions committed before the last
snapshot are hashed exactly once, and decisions after it are replayed
bit-identically from the restored state + RNG.

On top sits the **degradation ladder**
(``robust.guarded.DegradationLadder``): repeated guard trips or
exhausted launch retries step the job down ``bucketed -> minstop``,
``radix -> sort``, ``tag32 -> int64`` -- every rung an already-proven
exact path, so a degraded run is slower, never divergent.  Ladder
position rides in the checkpoint and in obs row
``degradation_ladder_steps``.

``EpochJob(engine_loop="stream")`` swaps the per-epoch launch
structure for the always-on streaming serve loop (``engine.stream``;
docs/ENGINE.md "engine_loop"): one fused ingest+serve+commit device
launch per checkpoint-boundary chunk, double-buffered superwave
pregen, drains only at the boundaries -- decisions digest-pinned
bit-identical to the round loop, and every invariant above (crash
equivalence, telemetry, the ladder) carries over unchanged
(``_stream_epochs``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time as _time
from typing import Callable, NamedTuple, Optional

import numpy as np

from ..utils import checkpoint as ckpt_mod
from .guarded import (RECOVERABLE_ERRORS, DegradationLadder,
                      run_epoch_guarded)
from .host_faults import (HostFaultInjector, HostFaultPlan, HostKill,
                          describe_host, plan_from_json, plan_to_json,
                          zero_host_plan)


class SupervisorGaveUp(RuntimeError):
    """The job died more times than ``max_restarts`` allows."""


@dataclasses.dataclass(frozen=True)
class EpochJob:
    """A deterministic, resumable epoch-loop workload -- the sim/bench
    inner loop distilled to what the supervisor needs: everything
    below is plain data, so a job JSON-round-trips into a spawned
    child process and two runs of the same job are bit-identical."""

    engine: str = "prefix"          # prefix | chain | calendar
    n: int = 512                    # clients
    depth: int = 12                 # preloaded queue depth
    ring: int = 16
    epochs: int = 8
    m: int = 4                      # batches per epoch
    k: int = 64                     # per-batch cap / calendar steps
    chain_depth: int = 4
    select_impl: str = "sort"
    tag_width: int = 64
    calendar_impl: str = "minstop"
    ladder_levels: int = 4
    wheel_kernel: str = "xla"       # wheel bucket kernel: xla | pallas
    seed: int = 11                  # arrival RNG seed
    arrival_lam: float = 2.0        # Poisson mean arrivals/client/epoch
    waves: int = 4
    dt_epoch_ns: int = 10 ** 8
    ckpt_every: int = 2             # checkpoint every N epochs
    keep: int = 4                   # rotation depth
    ladder: bool = False            # degradation ladder enabled
    ladder_threshold: int = 2
    metrics_port: Optional[int] = None   # scrape endpoint (fail-soft)
    # offset client 0's head proportion tag (ns): past +-2^31 it
    # deterministically trips the tag32 rebase window every epoch --
    # the in-repo way to exercise guard trips / ladder engagement
    tag_spread_ns: int = 0
    # device telemetry plane (obs.histograms / obs.flight): the
    # accumulators ride the rotation checkpoints, so crash equivalence
    # extends to telemetry (histograms + ledger + flight ring of a
    # killed-and-resumed run == the uninterrupted run, bit-identical)
    with_hists: bool = False        # log2 QoS histograms
    with_ledger: bool = False       # per-client conformance ledger
    flight_records: int = 0         # HBM flight-recorder rows (0=off)
    flight_dump: Optional[str] = None  # JSONL path the flight ring is
    #                                    dumped to when an incarnation
    #                                    crashes (--flight-dump)
    # time-domain tracing plane (obs.spans): span JSONL path, APPENDED
    # to at every checkpoint boundary -- and ONLY there: a resume
    # replays from the last snapshot, so flushing past it would
    # double-count the replayed epochs' spans.  The stream survives a
    # SIGKILL restart with exactly the rotation checkpoints'
    # durability window.  Spans are host-side wall time --
    # per-incarnation timestamps, deliberately OUTSIDE the
    # checkpointed state (crash equivalence is about decisions, not
    # about how long the host took)
    span_log: Optional[str] = None
    # client lifecycle plane (docs/LIFECYCLE.md): a churn spec dict
    # (lifecycle.churn.make_spec) turns the job into an OPEN-population
    # run -- the engine state starts EMPTY at the spec's capacity0 and
    # a lifecycle.LifecyclePlane drives registration / live QoS
    # updates / idle eviction / compaction at the ckpt_every boundary
    # grid (= the stream loop's chunk grid, so lifecycle ops compose
    # with the fused chunk by construction).  Arrivals come from the
    # spec's per-epoch lam vectors, drawn in CLIENT-ID space (identical
    # RNG consumption in a dynamic run and its static_variant -- the
    # digest gate's meaningfulness) and mapped onto the current slot
    # layout at each boundary.  The chain digest hashes the CANONICAL
    # client-id-space views (plane.canon_results), so registration
    # timing, slot recycling, growth, and compaction are digest-
    # neutral; the plane's state (slot map, pending-update journal,
    # WAL cursor, counters) rides the rotation checkpoints as lc_*
    # leaves, so churned runs stay crash-equivalent.  None = the
    # closed-population job the PRs 1-8 gates pin.
    churn: Optional[dict] = None
    # SLO plane (obs.slo / obs.alerts; docs/OBSERVABILITY.md "SLO
    # plane"): a per-client windowed-conformance block rides the epoch
    # scans like the PR-6 telemetry, with window rolls pinned to the
    # ckpt_every boundary grid (= the stream loop's chunk grid, so
    # both loops roll identically).  The closed-window ring, the
    # contract-epoch counters, and the burn-rate evaluator state ride
    # the rotation checkpoints as slo_* leaves -- crash equivalence
    # extends to all of them (a killed-and-resumed run's windows,
    # attribution, and fired episodes == the uninterrupted run's).
    with_slo: bool = False
    slo_ring: int = 64              # closed-window ring depth/client
    # judged closed windows as JSONL (scripts/slo_report.py's feed),
    # APPENDED right after each checkpoint commits -- the span_log
    # durability discipline: what is flushed is exactly what a resume
    # will never re-close
    slo_log: Optional[str] = None
    # decision provenance plane (obs.provenance;
    # docs/OBSERVABILITY.md "Provenance plane"): the per-batch "why"
    # block -- winner margins, limit-gate state, eligible-set depth,
    # winning phase, per-client last_served watermark + starvation
    # high-watermark -- rides the epoch scans like the PR-6
    # telemetry.  The block's leaves ride the rotation checkpoints
    # (prov_*), so crash equivalence extends to it bit-for-bit.
    # Composes with ``churn``: the per-slot last_served watermark
    # rides the lifecycle boundary as an extras rider with fill 0
    # (= never served), so a recycled slot's new tenant starts with
    # no inherited serve history and the dynamic==static digest gate
    # extends to the provenance plane.
    with_prov: bool = False
    # engine loop structure (docs/ENGINE.md "engine_loop"): "round"
    # launches the admission readback + ingest + epoch separately per
    # epoch (the PR-5 shape, ~3 host round-trips/epoch); "stream"
    # fuses ingest+serve+commit for EVERY epoch between two checkpoint
    # boundaries into ONE device launch (engine.stream), with the
    # decision stream / metrics / telemetry accumulating in HBM, the
    # host pre-generating chunk T+1's superwave draws while the device
    # runs chunk T (double buffer), and drains only at the PR-5
    # checkpoint boundaries.  Decisions are digest-pinned
    # bit-identical to "round" (ci.sh streaming smoke); a guard trip
    # inside a chunk falls back to the round path for that chunk
    # (robust.guarded.run_stream_chunk_guarded), so crash equivalence
    # and the degradation ladder survive unchanged.  "mesh" shards the
    # stream loop over a device mesh (parallel.mesh; docs/ENGINE.md
    # "Mesh serving"): ``n_shards`` full per-device engines each run
    # the complete fused chunk inside ONE shard_map launch, with the
    # paper's delta/rho counter views exchanged through a [C]-sized
    # psum at epoch boundaries on the ``counter_sync_every`` grid.
    # S=1 mesh is bit-identical to "stream" (and so to "round") by
    # construction -- both trace engine.stream.make_epoch_step -- and
    # the counter plane + per-shard telemetry ride the rotation
    # checkpoints, so crash equivalence extends to the mesh loop
    # unchanged.  ``churn`` composes via PER-SHARD lifecycle planes
    # (client ids routed by the placement map -- ``placement`` below;
    # the default static map IS ``cid % n_shards``; docs/LIFECYCLE.md
    # "Per-shard routing") and ``flight_records`` via per-shard HBM
    # rings merged in shard order at drain; mesh churn does not yet
    # compose with ``with_slo`` (the merged window table would need
    # an id-space merge across per-shard slot layouts -- rejected up
    # front) and composes with ``fault_plan`` only under
    # ``placement="p2c"``, where a registration routed to a DOWN
    # shard deterministically re-routes to its live sampled choice
    # (or defers one boundary when both are down); static placement
    # has no re-route path, so churn + fault_plan + static stays a
    # loud up-front ValueError.
    engine_loop: str = "round"
    # mesh serving plane knobs (engine_loop="mesh" only): shard count
    # (devices used; obs.capacity.plan_capacity sizes it from the
    # client target) and the counter-exchange staleness knob -- views
    # refresh from the mesh psum only on epochs where
    # ``epoch % counter_sync_every == 0`` (epoch 0 always syncs; the
    # paper's piggybacked views are naturally stale, so K>1 keeps the
    # QoS invariants -- parallel.cluster.run_mesh_rounds pins the
    # same knob decision-exact against the host loop's
    # delay_counters fault)
    n_shards: int = 1
    counter_sync_every: int = 1
    # shard placement plane (lifecycle/placement.py; docs/LIFECYCLE.md
    # "Placement and migration"; engine_loop="mesh" + churn only):
    # "static" keeps the historical ``cid % n_shards`` ownership
    # BIT-IDENTICALLY (no PlacementMap is even built); "p2c" routes
    # new registrations by power-of-two-choices over the per-shard
    # pressure backlog from a checkpointed placement RNG (scenario
    # pins keep shard_skew's scripted ownership), enables the
    # controller's ``migrate`` actuation (live digest-neutral
    # EVICT/REGISTER handoffs between shards), and lifts the
    # churn-with-fault_plan rejection (DOWN-shard registrations
    # re-route/defer deterministically).  A ``{"mode": "p2c",
    # "overrides": {cid: shard}}`` dict pins specific clients to
    # specific shards -- the digest gate's placed-from-start twin.
    placement: object = "static"
    # degraded-mode mesh serving (docs/ROBUSTNESS.md "Degraded-mode
    # mesh"; engine_loop="mesh" only): a JSON-able fault-plan SPEC
    # (dict, or its "seed=..,p_dropout=.." string form) --
    # ``robust.faults.parse_fault_spec`` keys: seed, p_dropout,
    # mean_outage_steps, p_delay, p_dup, max_skew_ns -- sampled
    # deterministically at job start into a ``FaultPlan`` over
    # (epochs, n_shards) and COMPILED INTO every fused mesh chunk as
    # traced per-epoch masks (parallel.mesh).  The plan is pure host
    # data recomputed per incarnation from this spec, so crash
    # equivalence needs no new checkpoint state; a guard trip during
    # a chaos chunk replays the identical schedule on the host robust
    # loop (counted as a mesh_chaos_fallback).  None = no fault
    # plumbing (byte-identical to the pre-chaos chunk program).
    fault_plan: object = None   # dict spec or
    #                             "seed=..,p_dropout=.." string
    # closed-loop serving controller (control/; docs/CONTROLLER.md):
    # a host control plane evaluated at the checkpoint-boundary grid
    # -- one typed ControlSignals snapshot per boundary (SLO burn,
    # backlog, capacity occupancy, starvation watermarks), a
    # deterministic guarded-transition policy with per-rule
    # hysteresis/cooldown, and a WAL-journaled knob vector (staleness,
    # ladder overlay, admission clamp, compaction trigger).  Every
    # decision is fsynced to the journal BEFORE it applies; a resumed
    # run REPLAYS journaled decisions instead of re-deciding, so
    # crash equivalence extends to the controller (kill at any
    # actuation stage == the uninterrupted twin, bit-identical).
    # Actuation routes only through exact-twin switches (ladder
    # rungs, device admission clamp, boundary compaction), so
    # ``controller=None`` (off) stays bit-identical to the bare
    # runner.  Accepts None/False (off), True (defaults), a
    # control.ControllerConfig, or its asdict() (JSON round-trip).
    controller: object = None

    def to_json(self) -> dict:
        # asdict recurses into a ControllerConfig, so a controller
        # job JSON round-trips into the spawn-mode child unchanged
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict) -> "EpochJob":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in obj.items() if k in fields})


class SupervisedResult(NamedTuple):
    """What a completed (bare or supervised) run reports."""

    digest: str         # hex decision-stream chain digest
    state_digest: str   # sha256 over the final engine state leaves
    decisions: int
    epochs: int
    metrics: np.ndarray  # int64[NUM_METRICS], resume row included
    restarts: int
    ladder_steps: list   # DegradationLadder.describe() rows
    # scrape-port rebinds observed by the FINAL incarnation (host
    # telemetry, deliberately outside the checkpointed state --
    # rebinds in killed incarnations die with them)
    scrape_rebinds: int
    # rotation path the FINAL incarnation resumed from (None when it
    # started fresh) -- the newest-intact-fallback observability hook
    resumed_from: Optional[str] = None
    # telemetry plane (None when the job ran with it off); numpy
    # arrays, compared bit-for-bit by the crash-equivalence gate
    hists: Optional[np.ndarray] = None      # [NUM_HISTS, BUCKETS+1]
    ledger: Optional[np.ndarray] = None     # [N, LED_COLS]
    flight_buf: Optional[np.ndarray] = None  # [R, FLIGHT_COLS]
    flight_seq: int = 0                      # records ever written
    # stream chunks that tripped a guard and re-ran on the round path
    # (engine_loop="stream" only; deterministic, so it replays to the
    # same value across a crash+resume)
    stream_fallbacks: int = 0
    # lifecycle-plane summary (plane.snapshot(): live/peak clients,
    # capacity, registration/eviction/compaction/qos-update counters)
    # for churn jobs; None for closed-population jobs.  Deterministic,
    # so the crash-equivalence gate compares it too.
    lifecycle: Optional[dict] = None
    # SLO plane outputs (None when the job ran with it off): the final
    # open window block, the closed-window ring (flat RING_COLS rows in
    # close order), the contract-epoch counters ([K, 2] cid/epoch
    # pairs), and the burn-rate evaluator summary -- all deterministic,
    # all compared by the crash-equivalence gate
    slo_window: Optional[np.ndarray] = None
    slo_ring: Optional[np.ndarray] = None
    slo_cepoch: Optional[np.ndarray] = None
    slo: Optional[dict] = None
    # provenance plane outputs (None when the job ran with it off):
    # the margin histogram row, the scalar aggregates, and the
    # per-client last_served watermark -- all deterministic, all
    # compared by the crash-equivalence gate
    prov_margin_hist: Optional[np.ndarray] = None
    prov_scal: Optional[np.ndarray] = None
    prov_last_served: Optional[np.ndarray] = None
    # mesh serving plane outputs (engine_loop="mesh" only; None
    # otherwise): the per-shard completion counters ([2, S, N]:
    # delta, rho) and the held counter views ([2, S, N]) -- both
    # deterministic, both compared by the crash-equivalence gate --
    # plus the chunk-fallback count (the stream_fallbacks analog)
    mesh_counters: Optional[np.ndarray] = None
    mesh_views: Optional[np.ndarray] = None
    mesh_fallbacks: int = 0
    # chaos chunks (fault_plan set) that tripped a guard and replayed
    # on the host robust loop -- the degraded-mode mesh's
    # slow-but-on-plan path (a subset of mesh_fallbacks)
    mesh_chaos_fallbacks: int = 0
    # closed-loop controller outputs (job.controller set; zeros/None
    # otherwise): applied decision count, final knob vector, and the
    # journaled decision trajectory [[seq, epoch, rule, knobs...]] --
    # all deterministic, all compared by the crash-equivalence gate.
    # controller_replays counts journal REPLAYS by the final
    # incarnation (legitimately nonzero only after a crash, like the
    # resume rows -- excluded from the gate).
    controller_decisions: int = 0
    controller_replays: int = 0
    controller_knobs: Optional[list] = None
    controller_trajectory: Optional[list] = None
    # shard placement / migration plane outputs (mesh churn with
    # placement != "static"; None/0 otherwise): the placement mode,
    # the migration count, the move log [[boundary, cid, src, dst]]
    # in move order (the digest gate's overrides source), and the
    # PlacementMap counter snapshot -- all deterministic (the
    # placement RNG rides the rotation checkpoints), all compared by
    # the crash-equivalence gate
    placement: Optional[str] = None
    migrations: int = 0
    migration_log: Optional[list] = None
    placement_counters: Optional[dict] = None


def assert_crash_equivalent(interrupted: SupervisedResult,
                            reference: SupervisedResult) -> None:
    """The digest gate: decision stream, final state, and metric
    totals must match bit-for-bit, modulo the resume rows an
    interrupted run legitimately grows."""
    from ..obs import device as obsdev

    assert interrupted.digest == reference.digest, \
        (f"decision digest diverged: {interrupted.digest[:16]} vs "
         f"{reference.digest[:16]}")
    assert interrupted.state_digest == reference.state_digest, \
        "final engine state diverged"
    assert interrupted.decisions == reference.decisions
    a = np.asarray(interrupted.metrics, dtype=np.int64).copy()
    b = np.asarray(reference.metrics, dtype=np.int64).copy()
    for row in obsdev.RESUME_ROWS:
        a[row] = b[row] = 0
    assert np.array_equal(a, b), \
        (f"metric totals diverged outside the resume rows: "
         f"{a.tolist()} vs {b.tolist()}")
    # crash equivalence extends to the telemetry plane: the
    # accumulators ride the rotation checkpoints and the replayed
    # decisions are bit-identical, so histograms, ledger, AND the
    # flight ring must match exactly (no resume-row exception -- the
    # telemetry plane has no host-restart counters)
    for field in ("hists", "ledger", "flight_buf"):
        x = getattr(interrupted, field)
        y = getattr(reference, field)
        assert (x is None) == (y is None), \
            f"telemetry field {field} enabled on only one side"
        if x is not None:
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"telemetry field {field} diverged across the crash"
    assert interrupted.flight_seq == reference.flight_seq
    # lifecycle state replays deterministically from the checkpointed
    # slot map + WAL cursor, so the full plane summary (population,
    # capacity, every counter) must match too
    assert interrupted.lifecycle == reference.lifecycle, \
        (f"lifecycle plane diverged across the crash: "
         f"{interrupted.lifecycle} vs {reference.lifecycle}")
    # the SLO plane's window block, closed-window ring, and
    # contract-epoch counters ride the rotation checkpoints and the
    # rolls are pinned to the checkpoint grid, so all three -- and the
    # burn-rate evaluator's episode accounting -- must be bit-identical
    for field in ("slo_window", "slo_ring", "slo_cepoch"):
        x = getattr(interrupted, field)
        y = getattr(reference, field)
        assert (x is None) == (y is None), \
            f"SLO field {field} enabled on only one side"
        if x is not None:
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"SLO field {field} diverged across the crash"
    assert interrupted.slo == reference.slo, \
        (f"SLO evaluator diverged across the crash: "
         f"{interrupted.slo} vs {reference.slo}")
    # the provenance block rides the rotation checkpoints and its
    # observations are pure functions of the replayed decisions, so
    # margin histogram, scalar aggregates, and the last_served
    # watermark must all be bit-identical too
    for field in ("prov_margin_hist", "prov_scal",
                  "prov_last_served"):
        x = getattr(interrupted, field)
        y = getattr(reference, field)
        assert (x is None) == (y is None), \
            f"provenance field {field} enabled on only one side"
        if x is not None:
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"provenance field {field} diverged across the crash"
    # the mesh counter plane (per-shard delta/rho completions + held
    # views) rides the rotation checkpoints and replays
    # deterministically, so both arrays must match bit-for-bit too
    for field in ("mesh_counters", "mesh_views"):
        x = getattr(interrupted, field)
        y = getattr(reference, field)
        assert (x is None) == (y is None), \
            f"mesh field {field} enabled on only one side"
        if x is not None:
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"mesh field {field} diverged across the crash"
    # the controller journals every decision BEFORE applying it and a
    # resumed run replays the journal instead of re-deciding, so the
    # applied count, the final knob vector, and the full decision
    # trajectory must be bit-identical (controller_replays is the one
    # legitimately-different field: it counts how many of those
    # decisions the final incarnation REPLAYED rather than made)
    assert interrupted.controller_decisions == \
        reference.controller_decisions, \
        (f"controller decision count diverged: "
         f"{interrupted.controller_decisions} vs "
         f"{reference.controller_decisions}")
    assert interrupted.controller_knobs == reference.controller_knobs, \
        (f"controller knob vector diverged: "
         f"{interrupted.controller_knobs} vs "
         f"{reference.controller_knobs}")
    assert interrupted.controller_trajectory == \
        reference.controller_trajectory, \
        (f"controller decision trajectory diverged: "
         f"{interrupted.controller_trajectory} vs "
         f"{reference.controller_trajectory}")
    # the placement map's RNG/assignment/move-log ride the rotation
    # checkpoints and migrations replay deterministically from the
    # journaled trigger + checkpointed RNG, so the whole plane -- the
    # move log included, in order -- must be bit-identical
    assert interrupted.placement == reference.placement, \
        "placement mode diverged across the crash"
    assert interrupted.migrations == reference.migrations, \
        (f"migration count diverged: {interrupted.migrations} vs "
         f"{reference.migrations}")
    assert interrupted.migration_log == reference.migration_log, \
        (f"migration log diverged: {interrupted.migration_log} vs "
         f"{reference.migration_log}")
    assert interrupted.placement_counters == \
        reference.placement_counters, \
        (f"placement counters diverged: "
         f"{interrupted.placement_counters} vs "
         f"{reference.placement_counters}")



# ----------------------------------------------------------------------
# the job loop
# ----------------------------------------------------------------------

def _job_state(job: EpochJob):
    """Deterministic preloaded engine state (the bench serve-only
    preload shape: staggered proportion tags, ``depth`` queued ops per
    client).  A churn job starts EMPTY at the spec's initial capacity
    instead -- its population arrives through the lifecycle plane.  A
    mesh job (``engine_loop="mesh"``) returns the STACKED ``[S, ...]``
    layout: every shard is one server owning a DISTINCT ``n``-client
    partition that shares this same contract layout (S * n client
    contracts across the mesh; independent per-shard arrival streams
    supply the divergence -- parallel.mesh module doc)."""
    import jax.numpy as jnp

    from ..core.timebase import rate_to_inv_ns
    from ..engine import init_state

    if job.engine_loop == "mesh":
        from ..parallel import mesh as mesh_mod

        single = dataclasses.replace(job, engine_loop="stream")
        return mesh_mod.stack_shards(_job_state(single), job.n_shards)
    if job.churn is not None:
        # open population: EMPTY at the spec's initial capacity (a
        # mesh churn job stacks S of these -- every shard starts at
        # the same capacity0, its partition arriving through its own
        # per-shard plane)
        return init_state(int(job.churn["capacity0"]), job.ring)
    st = init_state(job.n, job.ring)
    c = np.arange(job.n)
    rinv = np.full(job.n, rate_to_inv_ns(100.0), dtype=np.int64)
    winv = np.asarray([rate_to_inv_ns(1.0 + (i % 4)) for i in c],
                      dtype=np.int64)
    phase = ((c * 2654435761) & 0xFFFFF) / float(1 << 20)
    jitter = (phase * 2.0 * winv).astype(np.int64)
    if job.tag_spread_ns:
        jitter[0] += np.int64(job.tag_spread_ns)
    q_arr = np.zeros((job.n, job.ring), dtype=np.int64)
    q_arr[:, :job.depth - 1] = np.tile(np.arange(1, job.depth),
                                       (job.n, 1))
    return st._replace(
        active=jnp.ones(job.n, dtype=bool),
        idle=jnp.zeros(job.n, dtype=bool),
        order=jnp.arange(job.n, dtype=jnp.int64),
        resv_inv=jnp.asarray(rinv),
        weight_inv=jnp.asarray(winv),
        head_resv=jnp.asarray(rinv),
        head_prop=jnp.asarray(winv + jitter),
        head_limit=jnp.full(job.n, -(1 << 62), dtype=jnp.int64),
        depth=jnp.full(job.n, job.depth, dtype=jnp.int32),
        q_arrival=jnp.asarray(q_arr),
        q_cost=jnp.ones((job.n, job.ring), dtype=jnp.int64),
    )


def _rng_state_array(rng: np.random.Generator) -> np.ndarray:
    """PCG64 bit-generator state as uint64[6] (128-bit state and inc
    split lo/hi, plus the uint32 spill) -- checkpointable host RNG."""
    s = rng.bit_generator.state
    mask = (1 << 64) - 1
    st, inc = s["state"]["state"], s["state"]["inc"]
    return np.asarray([st & mask, (st >> 64) & mask,
                       inc & mask, (inc >> 64) & mask,
                       int(s["has_uint32"]), int(s["uinteger"])],
                      dtype=np.uint64)


def _rng_from_array(a) -> np.random.Generator:
    a = np.asarray(a, dtype=np.uint64)
    rng = np.random.Generator(np.random.PCG64(0))
    rng.bit_generator.state = {
        "bit_generator": "PCG64",
        "state": {"state": int(a[0]) | (int(a[1]) << 64),
                  "inc": int(a[2]) | (int(a[3]) << 64)},
        "has_uint32": int(a[4]), "uinteger": int(a[5])}
    return rng


_DIGEST_FIELDS = ("count", "unit_count", "resv_count", "slot", "cls",
                  "length", "phase", "cost", "lb", "served", "type")


def _digest_update(digest: bytes, results) -> bytes:
    """One chain-digest step: sha256(previous digest || this epoch's
    decision arrays).  Resumable where a single running sha256 is not:
    the 32-byte chain value rides in the checkpoint, decisions before
    the snapshot are hashed exactly once, decisions after it replay
    into the same chain."""
    import jax

    h = hashlib.sha256(digest)
    for r in results:
        for name in _DIGEST_FIELDS:
            if hasattr(r, name):
                a = np.asarray(jax.device_get(getattr(r, name)))
                h.update(str(a.dtype).encode())
                h.update(str(a.shape).encode())
                h.update(np.ascontiguousarray(a).tobytes())
    return h.digest()


def _tree_digest(tree) -> str:
    import jax

    return ckpt_mod._leaf_digest(
        [np.asarray(x) for x in jax.device_get(jax.tree.leaves(tree))])


def _payload(job: EpochJob, state, rng, met, digest: bytes,
             epoch: int, decisions: int, ladder_vec,
             hists=None, ledger=None, flight=None,
             plane=None, slo=None, prov=None, mesh=None,
             ctl=None, pm=None) -> dict:
    import jax

    from ..control import Controller
    from ..lifecycle.placement import PlacementMap
    from ..lifecycle.plane import LifecyclePlane
    from ..obs import flight as obsflight
    from ..obs import slo as obsslo
    from ..obs.alerts import SloEvaluator

    # telemetry leaves are ALWAYS present (zero-size when the job runs
    # with that accumulator off) so the restore template's structure
    # depends only on the job config, never on runtime state
    z = np.zeros((0,), dtype=np.int64)
    # rng may be the live Generator (round loop) or a state array
    # snapshot (stream loop: the double buffer draws chunk T+1 BEFORE
    # boundary T's save, so the live generator is ahead of the
    # boundary -- the snapshot taken after chunk T's own draws is what
    # must persist, or a resume would re-draw a different stream)
    rng_arr = np.asarray(rng, dtype=np.uint64) \
        if isinstance(rng, np.ndarray) else _rng_state_array(rng)
    # lifecycle leaves are ALWAYS present too (empty for closed-
    # population jobs) -- same structure-from-config convention; their
    # capacities vary at runtime, so churn jobs restore with
    # strict_shapes=False (utils.checkpoint).  A mesh churn job
    # carries a LIST of per-shard planes: each encodes under
    # lc_s{s}_* (S is job config, so the payload structure still
    # depends only on the config), the base lc_* leaves stay empty.
    if isinstance(plane, (list, tuple)):
        lc = dict(LifecyclePlane.empty_leaves())
        for s, pl in enumerate(plane):
            lc.update({f"lc_s{s}{k[2:]}": v
                       for k, v in pl.encode().items()})
    elif plane is not None:
        lc = plane.encode()
    else:
        lc = LifecyclePlane.empty_leaves()
    # SLO leaves follow the same always-present convention: the block,
    # the plane's ring/contract-epoch state, and the evaluator's
    # episode accounting (slo = (block, SloPlane, SloEvaluator) or
    # None); rolls are pinned to the checkpoint grid, so the saved
    # block is always a freshly-opened window
    if slo is not None:
        sl = {"slo_window": np.asarray(jax.device_get(slo[0]),
                                       dtype=np.int64),
              **slo[1].encode(), **slo[2].encode()}
    else:
        sl = {"slo_window": np.zeros((0, obsslo.W_FIELDS),
                                     dtype=np.int64),
              **obsslo.SloPlane.empty_leaves(),
              **SloEvaluator.empty_leaves()}
    # mesh counter-plane leaves (engine_loop="mesh"): per-shard
    # delta/rho completion counters + held views ([S, N] each) --
    # always present (zero-size otherwise), the structure-from-config
    # convention
    if mesh is not None:
        mz = {k: np.asarray(jax.device_get(v), dtype=np.int64)
              for k, v in zip(("mesh_cd", "mesh_cr", "mesh_vd",
                               "mesh_vr"), mesh)}
    else:
        mz = {k: np.zeros((0,), dtype=np.int64)
              for k in ("mesh_cd", "mesh_cr", "mesh_vd", "mesh_vr")}
    # controller leaves follow the same always-present convention:
    # the applied-decision cursor, the knob vector, and the policy
    # hysteresis/cooldown state (fixed shapes from the rule table, so
    # even the zero template matches exactly)
    ct = ctl.encode() if ctl is not None else Controller.empty_leaves()
    # placement-map leaves (mesh churn with placement != "static"):
    # assignment, placement RNG, counters, move log, deferred list --
    # always present (zero-size otherwise), the structure-from-config
    # convention; move-log/deferred axis 0 is runtime state, so such
    # jobs already restore with strict_shapes=False (churn)
    pmz = pm.encode() if pm is not None else PlacementMap.empty_leaves()
    return {**lc, **sl, **mz, **ct, **pmz,
            "digest": np.frombuffer(digest, dtype=np.uint8).copy(),
            "decisions": np.int64(decisions),
            "engine": state,
            "epoch": np.int64(epoch),
            "ladder": np.asarray(ladder_vec, dtype=np.int64),
            "metrics": np.asarray(met, dtype=np.int64),
            "rng": rng_arr,
            "tele_hists": z if hists is None
            else np.asarray(jax.device_get(hists), dtype=np.int64),
            "tele_ledger": z if ledger is None
            else np.asarray(jax.device_get(ledger), dtype=np.int64),
            "tele_flight_buf":
                np.zeros((0, obsflight.FLIGHT_COLS), dtype=np.int64)
                if flight is None
                else np.asarray(jax.device_get(flight.buf),
                                dtype=np.int64),
            # seq/batch are scalars on single-engine loops and [S]
            # arrays for the mesh's stacked per-shard rings
            "tele_flight_seq": np.int64(0) if flight is None
            else np.asarray(jax.device_get(flight.seq),
                            dtype=np.int64),
            "tele_flight_batch": np.int64(0) if flight is None
            else np.asarray(jax.device_get(flight.batch),
                            dtype=np.int64),
            "prov_margin_hist": z if prov is None
            else np.asarray(jax.device_get(prov.margin_hist),
                            dtype=np.int64),
            "prov_scal": z if prov is None
            else np.asarray(jax.device_get(prov.scal),
                            dtype=np.int64),
            "prov_last_served": z if prov is None
            else np.asarray(jax.device_get(prov.last_served),
                            dtype=np.int64)}


def _tele_init(job: EpochJob):
    """Fresh telemetry accumulators per the job's static flags.  A
    churn job's per-client ledger is sized to the spec's initial
    capacity (it grows with the state arrays at boundaries)."""
    from ..obs import flight as obsflight
    from ..obs import histograms as obshist
    from ..obs import provenance as obsprov

    n = int(job.churn["capacity0"]) if job.churn is not None else job.n
    hists = obshist.hist_zero() if job.with_hists else None
    ledger = obshist.ledger_zero(n) if job.with_ledger else None
    flight = obsflight.flight_init(job.flight_records) \
        if job.flight_records else None
    prov = obsprov.prov_init(n) if job.with_prov else None
    if job.engine_loop == "mesh":
        # per-shard accumulator stacks (each shard's epoch program
        # carries its own; hists/ledger/prov merge through their
        # mesh-reduce algebra on the way out, the flight rings merge
        # in shard order at drain)
        from ..parallel import mesh as mesh_mod

        def stk(acc):
            return None if acc is None \
                else mesh_mod.stack_shards(acc, job.n_shards)

        hists, ledger, prov, flight = (stk(hists), stk(ledger),
                                       stk(prov), stk(flight))
    return hists, ledger, flight, prov


def _placement_map(job: EpochJob, *, payload=None):
    """The shared :class:`~dmclock_tpu.lifecycle.placement
    .PlacementMap` of a mesh churn job with ``placement != "static"``
    -- None otherwise (the static path must stay byte-identical to
    the pre-placement mesh, so no map is even built).  Pins and
    overrides re-derive from the job config; the assignment array,
    placement RNG, counters, move log, and deferred list restore from
    the ``pm_*`` checkpoint leaves when a payload is given."""
    from ..lifecycle import placement as placement_mod

    mode, overrides = placement_mod.parse_placement(job.placement)
    if mode == "static" or job.churn is None \
            or job.engine_loop != "mesh":
        return None
    pm = placement_mod.PlacementMap(
        job.n_shards, int(job.churn["total_ids"]), mode=mode,
        seed=job.seed,
        pins=placement_mod.placement_pins(job.churn, job.n_shards),
        overrides=overrides)
    if payload is not None:
        pm.load(payload)
    return pm


def _mesh_planes(job: EpochJob, *, tracer=None, payload=None,
                 pm=None):
    """The per-shard lifecycle planes of a mesh churn job (client ids
    routed by the shared placement map ``pm`` when one exists, else
    by ``cid % n_shards`` -- ``lifecycle.slots.owner_shard``), fresh
    or restored from the namespaced ``lc_s{s}_*`` checkpoint leaves.
    Planes run WITHOUT a workdir: the admin WAL/API surface is
    single-shard, mesh churn is scripted-events-only (routing live
    control ops per shard is the remaining rack-scheduling item)."""
    from ..lifecycle.plane import LifecyclePlane

    planes = []
    for s in range(job.n_shards):
        if payload is not None:
            pre = f"lc_s{s}_"
            sub = {"lc_" + k[len(pre):]: v
                   for k, v in payload.items() if k.startswith(pre)}
            planes.append(LifecyclePlane.load(
                sub, job.churn, tracer=tracer,
                shard=(s, job.n_shards)))
        else:
            planes.append(LifecyclePlane(
                job.churn, tracer=tracer, shard=(s, job.n_shards)))
        if pm is not None:
            planes[-1].attach_placement(pm)
    return planes


def _payload_like(job: EpochJob) -> dict:
    from ..lifecycle.plane import LifecyclePlane
    from ..obs import device as obsdev

    hists, ledger, flight, prov = _tele_init(job)
    mesh = None
    if job.engine_loop == "mesh":
        from ..parallel import mesh as mesh_mod

        n0 = int(job.churn["capacity0"]) \
            if job.churn is not None else job.n
        mesh = mesh_mod.counter_init(job.n_shards, n0)
    # the SLO leaves' template stays the empty-leaf shape even for
    # with_slo jobs: their axis-0 sizes are runtime state (ring fill,
    # contract count), so such jobs restore with the axis-0-only
    # relaxation (trailing dims still gate) -- see _job_loop
    plane = None
    pm = _placement_map(job)
    if job.churn is not None:
        plane = _mesh_planes(job, pm=pm) \
            if job.engine_loop == "mesh" \
            else LifecyclePlane(job.churn)
    tmpl = _payload(job, _job_state(job),
                    np.random.Generator(np.random.PCG64(job.seed)),
                    np.zeros(obsdev.NUM_METRICS, dtype=np.int64),
                    b"\x00" * 32, 0, 0,
                    DegradationLadder().encode(),
                    hists=hists, ledger=ledger, flight=flight,
                    prov=prov, mesh=mesh, plane=plane, pm=pm)
    if job.engine_loop == "mesh" and job.with_slo:
        # a mesh job's saved window block is the STACKED per-shard
        # [S, N, W_FIELDS] layout -- the template must carry the rank
        # and trailing dims (axis 0 stays relaxed like every slo leaf)
        from ..obs import slo as obsslo

        tmpl["slo_window"] = np.zeros((0, job.n, obsslo.W_FIELDS),
                                      dtype=np.int64)
    return tmpl


def _slo_log_flush(slo_plane, slo_log, closed) -> None:
    """Append one roll's judged closed windows to the slo_log JSONL
    (fail-soft: telemetry must never kill the run) -- the ONE
    implementation both the round and the stream loop call right
    after their checkpoint commits, so the two durability
    disciplines cannot drift."""
    if not closed or not slo_log or slo_plane is None:
        return
    try:
        slo_plane.export_jsonl(slo_log, closed)
    except OSError as e:
        print(f"# supervisor: slo_log write failed: {e}",
              file=sys.stderr)


class _ScrapeCtl:
    """Scrape-endpoint lifecycle shared by the round and the stream
    loop: (re)bind at the loop's natural host points (every epoch for
    the round loop, every drained epoch for the stream loop), pin
    ephemeral ports, poll ``/healthz`` after a rebind, and honor the
    injector's port-loss points.  Host telemetry only -- deliberately
    outside the checkpointed state."""

    def __init__(self, port, start_epoch: int, on_bind=None):
        self.port = port
        self.start_epoch = start_epoch
        self.scrape = None
        self.rebinds = 0
        # called with the server after EVERY successful (re)bind --
        # how a churn job's admin control API (lifecycle.api) rides
        # the endpoint across port-loss faults: mounts are per-server,
        # so a rebind must re-mount
        self.on_bind = on_bind

    def tick(self, epoch: int, injector) -> None:
        from ..obs.registry import start_http_server

        if self.port is not None and self.scrape is None:
            self.scrape = start_http_server(port=self.port)
            if self.scrape is not None:
                self.port = self.scrape.port   # pin ephemeral binds
                if self.on_bind is not None:
                    self.on_bind(self.scrape)
                if epoch > self.start_epoch:
                    self.rebinds += 1
                    # a rebind is only a recovery if the new endpoint
                    # actually serves: poll /healthz (best-effort --
                    # telemetry must never kill the run it observes)
                    if not _healthz_ok(self.scrape):
                        print("# supervisor: scrape rebind on "
                              f"port {self.scrape.port} failed its "
                              "healthz probe", file=sys.stderr)
        if injector is not None and injector.drop_scrape(epoch) \
                and self.scrape is not None:
            self.scrape.close()      # the plan yanks the port; the
            self.scrape = None       # loop rebinds next tick

    def close(self) -> None:
        if self.scrape is not None:
            self.scrape.close()
            self.scrape = None


def _draw_counts(rng: np.random.Generator, job: EpochJob,
                 epochs: int) -> np.ndarray:
    """RAW per-epoch Poisson draws ``int32[epochs, N]`` in epoch order
    -- the identical ``rng.poisson(lam, n)`` consumption sequence the
    round loop makes, so pre-generating a chunk ahead (the double
    buffer) advances the generator exactly as per-epoch draws would."""
    return np.stack([rng.poisson(job.arrival_lam, job.n)
                     .astype(np.int32) for _ in range(epochs)])


_INGEST_JIT_CACHE: dict = {}


def _jit_ingest(job: EpochJob):
    """Jitted superwave ingest for this job's static shape (the
    engine/queue.py module-cache convention)."""
    key = (job.n, job.ring, job.waves, job.dt_epoch_ns)
    if key not in _INGEST_JIT_CACHE:
        import jax.numpy as jnp

        from ..engine import kernels
        from ..obs import compile_plane as _cplane

        waves, dt_wave = job.waves, job.dt_epoch_ns // job.waves
        cost = jnp.ones((job.n,), dtype=jnp.int64)

        def ingest(st, counts, t_base):
            wave_times = t_base + jnp.arange(waves,
                                             dtype=jnp.int64) * dt_wave
            return kernels.ingest_superwave(st, counts, wave_times,
                                            cost, cost, cost,
                                            anticipation_ns=0)

        _INGEST_JIT_CACHE[key] = _cplane.instrumented_jit(
            ingest, cache="supervisor.ingest", entry=key)
    return _INGEST_JIT_CACHE[key]


def _prov_extras(prov):
    """The provenance plane's lifecycle-boundary riders: the per-slot
    last_served watermark rides grow/evict/compact with fill 0 (=
    never served), so a recycled slot's new tenant inherits no serve
    history.  margin_hist and scal are population aggregates, not
    per-slot arrays -- they pass through boundaries untouched."""
    return None if prov is None else [(prov.last_served, 0)]


def _prov_restamp(prov, extras):
    if prov is None:
        return None
    from ..obs import provenance as obsprov

    return obsprov.prov_from_arrays(prov.margin_hist, prov.scal,
                                    extras[0][0])


def _boundary_with_prov(plane, state, b, every, ledger, slo_block,
                        prov):
    """One lifecycle boundary with every rider the supervisor carries
    (ledger, SLO block, provenance watermark) -- the single unpack
    point the round and stream loops share, so the extras discipline
    cannot drift between them."""
    extras = _prov_extras(prov)
    out = plane.boundary(state, b, every, ledger=ledger,
                         slo_block=slo_block, extras=extras)
    state, ledger = out[0], out[1]
    i = 2
    if slo_block is not None:
        slo_block = out[i]
        i += 1
    if extras is not None:
        prov = _prov_restamp(prov, out[i])
    return state, ledger, slo_block, prov


def _ctl_compact(plane, state, ledger, slo_block, prov, b: int):
    """The controller's ``compact`` actuation: an out-of-band
    compaction through the lifecycle plane's own boundary transform
    (digest-neutral by the PR-11 gate -- the chain digest hashes
    canonical client-id views).  Runs BEFORE the boundary's
    checkpoint save, so the snapshot holds the compacted layout and
    a replayed decision re-compacts the replayed layout
    deterministically."""
    extras = _prov_extras(prov)
    out = plane.force_compact(state, ledger=ledger,
                              slo_block=slo_block, extras=extras, b=b)
    state, ledger = out[0], out[1]
    i = 2
    if slo_block is not None:
        slo_block = out[i]
        i += 1
    if extras is not None:
        prov = _prov_restamp(prov, out[i])
    return state, ledger, slo_block, prov


def _job_loop(job: EpochJob, workdir: Optional[str],
              injector: Optional[HostFaultInjector]
              ) -> SupervisedResult:
    """Run the job to completion once (restore -> epochs -> return).
    ``workdir=None`` is the BARE runner: no restore, no checkpoints,
    no injector -- the uninterrupted reference the digest gate
    compares against."""
    import jax
    import jax.numpy as jnp

    from ..obs import device as obsdev
    from ..obs import spans as _spans

    from ..obs import flight as obsflight

    from ..lifecycle.placement import parse_placement
    _pl_mode, _ = parse_placement(job.placement)   # validates the spec
    if _pl_mode != "static" and (job.engine_loop != "mesh"
                                 or job.churn is None):
        raise ValueError(
            "EpochJob(placement='p2c') is the mesh churn placement "
            "plane (engine_loop='mesh' + churn=...): power-of-two-"
            "choices needs per-shard pressure to choose between and "
            "an open population to place")
    if job.engine_loop == "mesh":
        if job.churn is not None and job.with_slo:
            raise ValueError(
                "EpochJob(engine_loop='mesh', churn=...) does not "
                "compose with with_slo yet: the cluster-wide "
                "window_mesh_reduce table is slot-indexed, and "
                "per-shard slot layouts diverge under churn -- the "
                "merge needs an id-space scatter first")
        if job.churn is not None and job.fault_plan is not None \
                and _pl_mode == "static":
            raise ValueError(
                "EpochJob(engine_loop='mesh') does not compose churn "
                "with fault_plan under placement='static': a static "
                "map has no answer for a registration routed to a "
                "DOWN shard.  placement='p2c' does (re-route to the "
                "live sampled choice, defer one boundary when both "
                "are down) -- pass placement='p2c'")
        if job.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, "
                             f"got {job.n_shards}")
        if job.churn is not None and \
                job.churn.get("scenario") == "shard_skew" and \
                int(job.churn.get("n_shards", 0)) != job.n_shards:
            # the spec's hot-shard mask is cid % spec.n_shards; a
            # mismatched job would silently smear the melt across
            # shards instead of concentrating it on one
            raise ValueError(
                f"shard_skew spec was built for "
                f"n_shards={job.churn.get('n_shards')} but the job "
                f"runs {job.n_shards} shards -- pass "
                f"make_spec('shard_skew', n_shards={job.n_shards})")
    if job.fault_plan is not None:
        if job.engine_loop != "mesh":
            raise ValueError(
                "EpochJob(fault_plan=...) is the in-chunk mesh fault "
                "model (engine_loop='mesh'); the round/stream loops "
                "inject faults through robust.cluster.run_with_plan")
        from .faults import parse_fault_spec
        # parse_fault_spec accepts dicts AND "seed=7,p_dropout=.."
        # strings (the bench --fault-plan form); a plain LABEL parses
        # to None and is rejected here -- a label cannot seed a plan
        if parse_fault_spec(job.fault_plan) is None:
            raise ValueError(f"fault_plan spec did not parse: "
                             f"{job.fault_plan!r} (expected keys like "
                             f"seed=.., p_dropout=..)")
    state = _job_state(job)
    rng = np.random.Generator(np.random.PCG64(job.seed))
    met = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
    digest = b"\x00" * 32
    start_epoch = 0
    decisions = 0
    tracer = _spans.SpanTracer() if job.span_log else None
    if tracer is not None:
        # compile records ride the SAME per-incarnation span stream
        # (category "compile"), so they flush with the span_log at
        # checkpoint boundaries -- the rotation checkpoints'
        # durability window (docs/OBSERVABILITY.md capacity plane).
        # Compile walls are host-side per-incarnation facts, like
        # every other span: deliberately outside the checkpointed
        # state and the crash-equivalence comparison.
        from ..obs import compile_plane as _cplane
        _cplane.plane().set_tracer(tracer)
    ladder = DegradationLadder(enabled=job.ladder,
                               threshold=job.ladder_threshold,
                               tracer=tracer)
    hists, ledger, flight, prov = _tele_init(job)
    ckpt_dir = os.path.join(workdir, "ckpt") if workdir else None

    # the closed-loop controller (control/; docs/CONTROLLER.md):
    # built before the restore so ctl.load can pick up the applied
    # cursor/knobs/policy state from the checkpoint while the journal
    # (loaded from the workdir in the constructor) supplies the
    # decisions to replay
    from ..control import Controller, as_spec as _ctl_as_spec
    ctl = None
    _ctl_spec = _ctl_as_spec(job.controller)
    if _ctl_spec is not None:
        ctl = Controller(
            _ctl_spec, n=job.n, ring=job.ring,
            counter_sync_every=job.counter_sync_every,
            capacity0=int(job.churn["capacity0"])
            if job.churn is not None else 0,
            n_shards=job.n_shards,
            workdir=workdir)

    payload = None
    resumed_from = None
    if ckpt_dir is not None and ckpt_mod.rotation_paths(ckpt_dir):
        # a non-empty rotation means a previous incarnation died:
        # resume from the newest INTACT snapshot (walks past any
        # torn/corrupted-by-plan entries).  EVERY entry torn is the
        # worst case, not a dead end: replay from scratch is
        # deterministic, so the run stays crash-equivalent -- it just
        # pays the full recompute.
        try:
            with _spans.span(tracer, "supervisor.resume",
                             "checkpoint"):
                # churn payloads hold grow-on-demand arrays (engine
                # state, ledger, slot map, journals) whose capacities
                # the fresh template cannot predict -- dtype+rank
                # checked, shapes from the file (utils.checkpoint).
                # SLO payloads relax the same way: the ring fill and
                # contract count are runtime state (axis 0 only;
                # trailing dims -- RING_COLS, W_FIELDS -- still gate)
                payload, resumed_from = \
                    ckpt_mod.restore_pytree_rotating(
                        ckpt_dir, _payload_like(job),
                        strict_shapes=job.churn is None
                        and not job.with_slo)
        except ckpt_mod.CheckpointCorruptError:
            payload = None
    if payload is not None:
        # durable resume journal: MET_SUPERVISOR_RESUMES counts
        # restarts that actually restored a snapshot -- a
        # replay-from-scratch restart (all snapshots torn) is a
        # RESTART but not a RESUME, and the metric exists to tell the
        # two apart
        with open(os.path.join(workdir, RESUME_LOG), "a") as fh:
            fh.write(f"{resumed_from}\n")
        state = payload["engine"]
        rng = _rng_from_array(payload["rng"])
        met = np.asarray(jax.device_get(payload["metrics"]),
                         dtype=np.int64).copy()
        digest = np.asarray(payload["digest"],
                            dtype=np.uint8).tobytes()
        start_epoch = int(payload["epoch"])
        decisions = int(payload["decisions"])
        ladder.load(jax.device_get(payload["ladder"]))
        # telemetry resumes from the snapshot too -- that is what
        # makes crash equivalence extend to the telemetry plane
        if job.with_hists:
            hists = jnp.asarray(payload["tele_hists"])
        if job.with_ledger:
            ledger = jnp.asarray(payload["tele_ledger"])
        if job.flight_records:
            flight = obsflight.flight_from_arrays(
                payload["tele_flight_buf"],
                payload["tele_flight_seq"],
                payload["tele_flight_batch"])
        if job.with_prov:
            from ..obs import provenance as obsprov
            # works for the stacked per-shard mesh blocks too --
            # jnp.asarray keeps the [S, ...] leading axis
            prov = obsprov.prov_from_arrays(
                payload["prov_margin_hist"], payload["prov_scal"],
                payload["prov_last_served"])
        if ctl is not None:
            # the applied cursor can only TRAIL the journal (fsync-
            # before-apply), so loading both re-arms the replay path
            # for every journaled-but-unapplied decision
            ctl.load(payload)

    mesh_ctrs = None
    if job.engine_loop == "mesh":
        from ..parallel import mesh as mesh_mod
        if payload is not None:
            mesh_ctrs = tuple(
                jnp.asarray(payload[k])
                for k in ("mesh_cd", "mesh_cr", "mesh_vd", "mesh_vr"))
        else:
            # per-slot counters follow the SLOT layout: a churn job's
            # slots start at the spec's capacity0 and grow/permute
            # with each shard's boundary (the extras discipline)
            n0 = int(job.churn["capacity0"]) \
                if job.churn is not None else job.n
            mesh_ctrs = mesh_mod.counter_init(job.n_shards, n0)

    plane = None
    mesh_planes = None
    pm = None
    if job.churn is not None and job.engine_loop == "mesh":
        pm = _placement_map(job, payload=payload)
        mesh_planes = _mesh_planes(job, tracer=tracer,
                                   payload=payload, pm=pm)
    elif job.churn is not None:
        from ..lifecycle.plane import LifecyclePlane
        if payload is not None:
            plane = LifecyclePlane.load(payload, job.churn,
                                        workdir=workdir, tracer=tracer)
        else:
            plane = LifecyclePlane(job.churn, workdir=workdir,
                                   tracer=tracer)

    # the SLO plane (obs.slo): window block + contract-epoch/ring host
    # state + burn-rate evaluator.  Window rolls happen ONLY at the
    # ckpt_every boundary grid below, in bare and supervised runs
    # alike -- the zero-host-fault gate compares their rings.
    slo_block = slo_plane = slo_eval = None
    slo_w0 = start_epoch
    if job.with_slo:
        import jax.numpy as _jnp

        from ..obs import slo as obsslo
        from ..obs.alerts import SloEvaluator

        if payload is not None:
            slo_block = _jnp.asarray(payload["slo_window"])
            # shape[-2] not [0]: a mesh job's block is the stacked
            # per-shard [S, N, W_FIELDS] layout
            slo_plane = obsslo.SloPlane.load(
                payload, capacity=int(slo_block.shape[-2]),
                dt_epoch_ns=job.dt_epoch_ns,
                ring_depth=max(job.slo_ring, 1))
            slo_eval = SloEvaluator(slo_plane)
            slo_eval.load(payload)
        else:
            n0 = int(job.churn["capacity0"]) if job.churn is not None \
                else job.n
            slo_plane = obsslo.SloPlane(n0,
                                        dt_epoch_ns=job.dt_epoch_ns,
                                        ring_depth=job.slo_ring)
            slo_block = obsslo.window_zero(n0)
            if job.churn is None:
                # closed population: every slot is a client with a
                # fixed contract, registered once from the device
                # truth (the inverse-rate arrays; a mesh job reads
                # shard 0 -- every partition shares one contract
                # layout, and the rolled table aggregates the S
                # like-contracted clients per slot)
                inv = state
                if job.engine_loop == "mesh":
                    from ..parallel import mesh as mesh_mod
                    inv = mesh_mod.unstack_shard(state)
                slo_plane.register_from_inv(
                    inv.resv_inv, inv.weight_inv, inv.limit_inv)
                slo_block = slo_plane.stamp(slo_block)
            if job.engine_loop == "mesh":
                # every shard carries its own block; the plane rolls
                # the window_mesh_reduce merge (cluster-wide table)
                from ..parallel import mesh as mesh_mod
                slo_block = mesh_mod.stack_shards(slo_block,
                                                  job.n_shards)
            slo_eval = SloEvaluator(slo_plane)
        if plane is not None:
            # lifecycle REGISTER/UPDATE/EVICT bump contract epochs
            # through the plane's boundary (docs/LIFECYCLE.md)
            plane.attach_slo(slo_plane)

    def _slo_roll(state_now, e1: int):
        """Close the window ending at boundary ``e1`` and judge it;
        returns the rows to flush AFTER the checkpoint commits."""
        nonlocal slo_block, slo_w0
        cid_of_slot = plane.slots.cid_of_slot if plane is not None \
            else None
        slo_block, closed = slo_plane.roll(
            slo_block, slo_w0, e1, cid_of_slot=cid_of_slot,
            depth=state_now.depth)
        slo_w0 = e1
        slo_eval.observe_roll(closed)
        return closed

    if ctl is not None:
        # pin the delta baselines to the RESTORED accumulators: the
        # previous boundary's snapshot is exactly what the killed
        # incarnation's controller last observed, so replayed
        # boundaries recollect identical signal deltas
        ctl.observe_baseline(met=met, slo_eval=slo_eval)
        from ..control import publish_controller
        from ..obs.registry import default_registry
        publish_controller(default_registry(), ctl)

    on_bind = None
    if plane is not None or slo_eval is not None:
        def on_bind(server, _plane=plane):
            # live control surface: the admin API (POST/PUT/DELETE
            # /clients...) + lifecycle counters ride the supervised
            # run's own scrape endpoint, re-mounted on every rebind.
            # Ops accepted here are WAL-fsynced (the plane has the
            # workdir), so a SIGKILL between accept and the epoch
            # boundary still applies them exactly once on resume.
            if _plane is not None:
                from ..lifecycle.api import mount_admin_api
                mount_admin_api(server, _plane, slo=slo_plane)
            if slo_eval is not None:
                from ..obs.alerts import mount_slo_api
                mount_slo_api(server, slo_eval)
    scr = _ScrapeCtl(job.metrics_port, start_epoch, on_bind)
    base_cfg = {"select_impl": job.select_impl,
                "tag_width": job.tag_width,
                "calendar_impl": job.calendar_impl}
    stream_fallbacks = 0

    if job.engine_loop == "stream":
        return _stream_epochs(job, injector, ckpt_dir, scr,
                              base_cfg, state, rng, met, digest,
                              start_epoch, decisions, ladder, tracer,
                              hists, ledger, flight, prov,
                              resumed_from, plane, slo_block,
                              slo_plane, slo_eval, ctl)
    if job.engine_loop == "mesh":
        return _mesh_epochs(job, injector, ckpt_dir, scr, base_cfg,
                            state, rng, met, digest, start_epoch,
                            decisions, ladder, tracer, hists, ledger,
                            flight, prov, resumed_from, slo_block,
                            slo_plane, slo_eval, mesh_ctrs,
                            mesh_planes, ctl, pm)
    assert job.engine_loop == "round", job.engine_loop
    ingest = _jit_ingest(job) \
        if job.arrival_lam > 0 and plane is None else None
    if plane is not None:
        from ..engine import stream as stream_mod
        from ..lifecycle import churn as churn_mod
        # the stream chunk's standalone ingest leg: the admission
        # clamp runs ON DEVICE with the identical integer math, so a
        # churn job's round loop is bit-identical to its stream loop
        churn_ingest = stream_mod.jit_ingest_step(
            dt_epoch_ns=job.dt_epoch_ns, waves=job.waves)

    try:
        for epoch in range(start_epoch, job.epochs):
            # epoch span entered/exited explicitly: the loop body
            # stays flat, and a crash mid-epoch simply leaves the span
            # open -- the tracer dies with the incarnation and the
            # flushed stream keeps every COMPLETED epoch (the same
            # at-most-one-epoch-lost window as the checkpoints)
            _ep_span = _spans.span(tracer, "supervisor.epoch",
                                   "host_prep", epoch=epoch)
            _ep_span.__enter__()
            scr.tick(epoch, injector)

            # lifecycle boundary: registration / QoS updates / idle
            # eviction / compaction apply BEFORE the window they
            # precede, on the ckpt_every grid (= the stream loop's
            # chunk grid), so a resume replaying this epoch re-applies
            # the identical ops from the checkpointed plane state
            if plane is not None and epoch % job.ckpt_every == 0:
                with _spans.span(tracer, "lifecycle.boundary",
                                 "host_prep", epoch=epoch):
                    state, ledger, slo_block, prov = \
                        _boundary_with_prov(plane, state, epoch,
                                            job.ckpt_every, ledger,
                                            slo_block, prov)

            t_base = jnp.int64(epoch * job.dt_epoch_ns)
            if plane is not None:
                with _spans.span(tracer, "supervisor.ingest",
                                 "ingest"):
                    raw = rng.poisson(churn_mod.lam_vector(
                        job.churn, epoch)).astype(np.int32)
                    counts = plane.map_counts(raw)
                    if ctl is not None:
                        # admission clamp AFTER the draws: the RNG
                        # consumption never depends on the knob, so
                        # controller on/off replays one arrival stream
                        counts = ctl.clamp_counts(counts, job.waves)
                    state = churn_ingest(
                        state, jnp.asarray(counts), t_base)
            elif ingest is not None:
                with _spans.span(tracer, "supervisor.ingest",
                                 "ingest"):
                    headroom = job.ring - np.asarray(
                        jax.device_get(state.depth), dtype=np.int64)
                    counts = np.minimum(
                        rng.poisson(job.arrival_lam, job.n),
                        np.minimum(headroom, job.waves)
                    ).astype(np.int32)
                    if ctl is not None:
                        counts = ctl.clamp_counts(counts, job.waves)
                    state = ingest(state, jnp.asarray(counts), t_base)
            while True:
                cfg = ladder.apply(ctl.overlay(base_cfg)
                                   if ctl is not None else base_cfg)
                try:
                    ep = run_epoch_guarded(
                        state,
                        epoch * job.dt_epoch_ns + job.dt_epoch_ns,
                        engine=job.engine, m=job.m, k=job.k,
                        chain_depth=job.chain_depth, with_metrics=True,
                        select_impl=cfg["select_impl"],
                        tag_width=cfg["tag_width"],
                        calendar_impl=cfg["calendar_impl"],
                        ladder_levels=job.ladder_levels,
                        wheel_kernel=job.wheel_kernel,
                        hists=hists, ledger=ledger, flight=flight,
                        slo=slo_block, prov=prov, tracer=tracer)
                    break
                except RECOVERABLE_ERRORS:
                    # bounded retries EXHAUSTED inside the guarded
                    # runner -- the ladder's launch-failure signal
                    # (recovered retries, ep.retries > 0, are NOT an
                    # escalation: the launch succeeded).  Each failed
                    # ATTEMPT counts toward the threshold, so the
                    # escalation is reachable at any threshold:
                    # below it the same path is re-attempted, at it a
                    # rung steps down, and with nothing left to
                    # concede (or the ladder off) the error surfaces
                    # to the supervisor's restart loop -- at most
                    # threshold * rungs attempts per epoch.
                    if not ladder.can_step(cfg):
                        raise
                    met[obsdev.MET_LADDER_STEPS] += \
                        ladder.note_epoch(cfg, launch_failures=1)
            state = ep.state
            decisions += ep.count
            if job.with_hists:
                hists = ep.hists
            if job.with_ledger:
                ledger = ep.ledger
            if job.flight_records:
                flight = ep.flight
            if job.with_prov:
                prov = ep.prov
            if job.with_slo:
                slo_block = ep.slo
            with _spans.span(tracer, "supervisor.digest", "drain"):
                # churn digests hash the CANONICAL client-id-space
                # views: slot layout (registration timing, recycling,
                # growth, compaction) must be digest-neutral
                digest = _digest_update(
                    digest, plane.canon_results(ep.results)
                    if plane is not None else ep.results)
                for r in ep.results:
                    if hasattr(r, "metrics"):
                        met = obsdev.metrics_combine_np(
                            met, jax.device_get(r.metrics))
            stepped = ladder.note_epoch(
                cfg,
                guard_trips=ep.rebase_fallbacks + ep.serial_fallbacks)
            met[obsdev.MET_LADDER_STEPS] += stepped

            if injector is not None:
                injector.after_decisions(decisions)
            at_boundary = ((epoch + 1) % job.ckpt_every == 0
                           or epoch + 1 == job.epochs)
            closed = None
            if slo_plane is not None and at_boundary:
                # the window roll happens in BARE and supervised runs
                # alike (same grid), BEFORE the snapshot: the saved
                # block is a freshly-opened window and the ring
                # already holds what this boundary closed
                closed = _slo_roll(state, epoch + 1)
            if ctl is not None and at_boundary:
                # the controller boundary: collect one typed signal
                # snapshot, run the guarded-transition policy (journal
                # fsyncs before every apply; a resumed run replays),
                # then actuate -- all BEFORE the snapshot, so the
                # checkpoint holds the post-actuation knobs/state
                sig = ctl.collect(
                    epoch + 1, state=state, met=met,
                    slo_eval=slo_eval, prov=prov,
                    planes=None if plane is None else [plane])
                fired = ctl.step(
                    epoch + 1, sig,
                    fault=None if injector is None
                    else injector.controller_point)
                if "compact" in fired and plane is not None:
                    state, ledger, slo_block, prov = _ctl_compact(
                        plane, state, ledger, slo_block, prov,
                        epoch + 1)
            if ckpt_dir is not None and at_boundary:
                with _spans.span(tracer, "supervisor.checkpoint_save",
                                 "checkpoint", epoch=epoch + 1):
                    payload = _payload(job, state, rng, met, digest,
                                       epoch + 1, decisions,
                                       ladder.encode(), hists=hists,
                                       ledger=ledger, flight=flight,
                                       prov=prov, plane=plane,
                                       slo=None if slo_plane is None
                                       else (slo_block, slo_plane,
                                             slo_eval), ctl=ctl)

                    def save(payload=payload):
                        return ckpt_mod.save_pytree_rotating(
                            ckpt_dir, payload, keep=job.keep)

                    if injector is not None:
                        injector.around_save(epoch, save)
                    else:
                        save()
                _ep_span.__exit__(None, None, None)
                # flush spans ONLY at checkpoint boundaries, right
                # after the snapshot commits: a resume replays from
                # the last checkpoint, so any span flushed PAST it
                # would appear twice in the stream after a
                # crash+resume (replayed epochs re-record).  Spans and
                # checkpoints share one durability window by
                # construction: what is flushed is exactly what will
                # never be replayed.  The slo_log flush follows the
                # same discipline: windows flushed after the save are
                # exactly the ones a resume will never re-close.
                if tracer is not None:
                    tracer.drain_jsonl(job.span_log)
                _slo_log_flush(slo_plane, job.slo_log, closed)
            else:
                _ep_span.__exit__(None, None, None)
                if ckpt_dir is None:
                    _slo_log_flush(slo_plane, job.slo_log, closed)
                if tracer is not None and ckpt_dir is None:
                    # bare/unsupervised runner: nothing ever replays,
                    # per-epoch flushes are safe
                    tracer.drain_jsonl(job.span_log)
    except BaseException:
        # the crash hook: dump the flight ring's last R commit
        # records before the incarnation dies (--flight-dump).  Best
        # effort -- the dump must never mask the original error.
        if job.flight_dump and flight is not None:
            try:
                n = obsflight.flight_dump(flight, job.flight_dump)
                print(f"# supervisor: dumped {n} flight records to "
                      f"{job.flight_dump}", file=sys.stderr)
            except Exception:
                pass
        # deliberately NO span flush here: rows recorded since the
        # last checkpoint boundary describe epochs a resume will
        # REPLAY, and flushing them would double-count those epochs
        # in the stream.  Un-flushed spans die with the incarnation --
        # exactly the checkpoint durability window the span_log
        # contract documents.
        raise
    finally:
        scr.close()

    if tracer is not None:   # e.g. a resume landing past the last
        tracer.drain_jsonl(job.span_log)  # epoch records only the
    #                                       resume span
    return _build_result(job, state, digest, decisions, met, ladder,
                         scr.rebinds, resumed_from, hists, ledger,
                         flight, stream_fallbacks, plane,
                         slo_block, slo_plane, slo_eval, prov,
                         ctl=ctl)


def _build_result(job, state, digest, decisions, met, ladder,
                  scrape_rebinds, resumed_from, hists, ledger, flight,
                  stream_fallbacks: int, plane=None,
                  slo_block=None, slo_plane=None,
                  slo_eval=None, prov=None, mesh=None,
                  mesh_fallbacks: int = 0,
                  mesh_chaos_fallbacks: int = 0,
                  ctl=None, pm=None) -> SupervisedResult:
    import jax

    slo_kw = {}
    if ctl is not None:
        slo_kw.update(
            controller_decisions=int(ctl.applied),
            controller_replays=int(ctl.replays),
            controller_knobs=[int(k) for k in ctl.knobs],
            controller_trajectory=ctl.trajectory())
    if pm is not None:
        slo_kw.update(
            placement=pm.mode,
            migrations=int(pm.counters["migrations"]),
            migration_log=pm.move_log(),
            placement_counters={k: int(v)
                                for k, v in pm.counters.items()})
    if mesh is not None and job.n_shards == 1:
        # S=1 canonicalization: a 1-shard mesh IS a single engine, so
        # the result (state digest, telemetry blocks, window block,
        # flight ring) drops the unit shard axis and the bit-identity
        # gate against the round/stream loops compares like for like
        from ..parallel import mesh as mesh_mod

        state = mesh_mod.unstack_shard(state)
        hists = None if hists is None else hists[0]
        ledger = None if ledger is None else ledger[0]
        prov = None if prov is None else mesh_mod.unstack_shard(prov)
        flight = None if flight is None \
            else mesh_mod.unstack_shard(flight)
        if slo_block is not None:
            slo_block = slo_block[0]
    elif mesh is not None and flight is not None:
        # S>1: merge the per-shard rings in DETERMINISTIC shard order
        # at drain -- each shard's valid rows in seq order, shards
        # concatenated 0..S-1 (obs.flight.flight_merge_stacked); the
        # crash-equivalence gate compares the merged rows, seq is the
        # cluster total
        from ..obs import flight as obsflight

        buf, seq = obsflight.flight_merge_stacked(flight)
        flight = obsflight.FlightState(
            buf=buf, seq=seq, batch=np.asarray(
                jax.device_get(flight.batch)).sum())
    if mesh is not None:
        cd, cr, vd, vr = [np.asarray(jax.device_get(x),
                                     dtype=np.int64) for x in mesh]
        slo_kw.update(mesh_counters=np.stack([cd, cr]),
                      mesh_views=np.stack([vd, vr]),
                      mesh_fallbacks=mesh_fallbacks,
                      mesh_chaos_fallbacks=mesh_chaos_fallbacks)
    if prov is not None:
        slo_kw.update(
            prov_margin_hist=np.asarray(
                jax.device_get(prov.margin_hist), dtype=np.int64),
            prov_scal=np.asarray(jax.device_get(prov.scal),
                                 dtype=np.int64),
            prov_last_served=np.asarray(
                jax.device_get(prov.last_served), dtype=np.int64))
    if slo_plane is not None:
        enc = slo_plane.encode()
        # update, never rebind: the provenance entries added above
        # must survive a job that runs BOTH planes
        slo_kw.update(
            slo_window=np.asarray(jax.device_get(slo_block),
                                  dtype=np.int64),
            slo_ring=enc["slo_ring"],
            slo_cepoch=enc["slo_cepoch"],
            slo=slo_eval.summary())
    if isinstance(plane, (list, tuple)):
        # mesh churn: one snapshot per shard (deterministic, so the
        # crash-equivalence dict compare still bites) + the cluster
        # rollup the bench/result consumers read
        shots = [p.snapshot() for p in plane]
        lifecycle = {
            "live_clients": sum(s["live_clients"] for s in shots),
            "peak_clients": sum(s["peak_clients"] for s in shots),
            "capacity": sum(s["capacity"] for s in shots),
            **{key: sum(s[key] for s in shots)
               for key in shots[0]
               if key not in ("live_clients", "peak_clients",
                              "capacity", "pending_ops")},
            "pending_ops": sum(s["pending_ops"] for s in shots),
            "shards": shots,
        }
    else:
        lifecycle = plane.snapshot() if plane is not None else None
    return SupervisedResult(
        **slo_kw,
        lifecycle=lifecycle,
        digest=hashlib.sha256(digest).hexdigest(),
        state_digest=_tree_digest(state),
        decisions=decisions, epochs=job.epochs,
        metrics=met, restarts=0,
        ladder_steps=ladder.describe(),
        scrape_rebinds=scrape_rebinds,
        resumed_from=resumed_from,
        hists=None if hists is None
        else np.asarray(jax.device_get(hists), dtype=np.int64),
        ledger=None if ledger is None
        else np.asarray(jax.device_get(ledger), dtype=np.int64),
        flight_buf=None if flight is None
        else np.asarray(jax.device_get(flight.buf), dtype=np.int64),
        flight_seq=0 if flight is None else int(flight.seq),
        stream_fallbacks=stream_fallbacks)


def _draw_counts_churn(rng: np.random.Generator, spec: dict,
                       e0: int, e1: int) -> np.ndarray:
    """RAW per-epoch Poisson draws for a churn spec,
    ``int32[e1 - e0, total_ids]`` in CLIENT-ID space and epoch order
    -- the identical consumption sequence in a dynamic run, its
    static variant, and both engine loops (the draw stays in id
    space; the slot mapping happens at the boundary, after the plane
    has applied it)."""
    from ..lifecycle import churn as churn_mod

    return np.stack([rng.poisson(churn_mod.lam_vector(spec, e))
                     .astype(np.int32) for e in range(e0, e1)])


def _stream_epochs(job: EpochJob, injector, ckpt_dir,
                   scr: _ScrapeCtl, base_cfg: dict, state, rng, met,
                   digest: bytes, start_epoch: int, decisions: int,
                   ladder, tracer, hists, ledger, flight, prov,
                   resumed_from, plane=None, slo_block=None,
                   slo_plane=None, slo_eval=None,
                   ctl=None) -> SupervisedResult:
    """The always-on streaming serve loop (docs/ENGINE.md
    "engine_loop"): one fused device launch per stream chunk (= the
    epochs between two PR-5 checkpoint boundaries), with the host
    pre-generating chunk T+1's superwave draws while the device runs
    chunk T and draining the HBM-accumulated decision stream /
    metrics / telemetry only at the boundary.

    Crash-equivalence discipline: the RNG state that rides each
    boundary's checkpoint is the snapshot taken right after THAT
    chunk's draws -- the double buffer's lookahead draws stay out of
    the persisted state, so a resumed incarnation re-draws them
    bit-identically.  The per-epoch drain bookkeeping (chain digest,
    metric fold, ladder notes, injector kill points) is the round
    loop's, run over the drained per-epoch rows in epoch order."""
    import jax

    from ..engine import stream as stream_mod
    from ..obs import device as obsdev
    from ..obs import flight as obsflight
    from ..obs import spans as _spans
    from .guarded import run_stream_chunk_guarded

    stream_fallbacks = 0
    do_ingest = job.arrival_lam > 0 or plane is not None
    slo_w0 = start_epoch
    try:
        counts = None
        rng_ckpt = _rng_state_array(rng)
        if do_ingest and start_epoch < job.epochs:
            with _spans.span(tracer, "stream.pregen", "host_prep"):
                e1 = next(stream_mod.chunk_bounds(
                    start_epoch, job.epochs, job.ckpt_every))[1]
                counts = _draw_counts_churn(
                    rng, job.churn, start_epoch, e1) \
                    if plane is not None \
                    else _draw_counts(rng, job, e1 - start_epoch)
            rng_ckpt = _rng_state_array(rng)
        for e0, b in stream_mod.chunk_bounds(start_epoch, job.epochs,
                                             job.ckpt_every):
            # bind/maintain the scrape endpoint BEFORE the fused
            # launch: the round loop serves /metrics from epoch 0, and
            # a first chunk can run for seconds -- the drain-time
            # per-epoch ticks below only honor the plan's port-loss
            # points (drop_scrape fires exactly once, so this pre-tick
            # cannot double-fire them)
            scr.tick(e0, injector)
            # lifecycle boundary at the chunk start: e0 is on the
            # ckpt_every grid by construction (chunk_bounds), so
            # lifecycle ops compose with the fused chunk by applying
            # only between launches -- the chunk itself never changes.
            # Slot mapping of the pre-generated ID-SPACE draws happens
            # HERE, after the boundary's registrations/evictions/
            # growth/compaction settled the layout for the chunk.
            if plane is not None:
                with _spans.span(tracer, "lifecycle.boundary",
                                 "host_prep", epoch=e0):
                    state, ledger, slo_block, prov = \
                        _boundary_with_prov(plane, state, e0,
                                            job.ckpt_every, ledger,
                                            slo_block, prov)
                counts_dev = plane.map_counts(counts)
            else:
                counts_dev = counts
            if ctl is not None and counts_dev is not None:
                # the whole chunk admits under the knob set at ITS
                # starting boundary -- exactly the per-epoch clamp the
                # round loop applies, because the knob only moves at
                # the controller boundaries (= the chunk grid)
                counts_dev = ctl.clamp_counts(counts_dev, job.waves)
            # the double buffer: chunk T+1's draws happen between the
            # chunk launch's dispatch and its device wait (the overlap
            # seam run_stream_chunk_guarded exposes).  Idempotent: a
            # retried launch must not re-advance the generator.
            nxt: dict = {}

            def overlap(b=b):
                if "rng" in nxt:
                    return
                if do_ingest and b < job.epochs:
                    with _spans.span(tracer, "stream.pregen",
                                     "host_prep"):
                        b1 = next(stream_mod.chunk_bounds(
                            b, job.epochs, job.ckpt_every))[1]
                        nxt["counts"] = _draw_counts_churn(
                            rng, job.churn, b, b1) \
                            if plane is not None \
                            else _draw_counts(rng, job, b1 - b)
                nxt["rng"] = _rng_state_array(rng)

            while True:
                cfg = ladder.apply(ctl.overlay(base_cfg)
                                   if ctl is not None else base_cfg)
                try:
                    g = run_stream_chunk_guarded(
                        state, e0, counts_dev, engine=job.engine,
                        epochs=b - e0, m=job.m, k=job.k,
                        chain_depth=job.chain_depth,
                        dt_epoch_ns=job.dt_epoch_ns, waves=job.waves,
                        with_metrics=True,
                        select_impl=cfg["select_impl"],
                        tag_width=cfg["tag_width"],
                        calendar_impl=cfg["calendar_impl"],
                        ladder_levels=job.ladder_levels,
                        wheel_kernel=job.wheel_kernel,
                        hists=hists, ledger=ledger, flight=flight,
                        slo=slo_block, prov=prov, tracer=tracer,
                        overlap=overlap)
                    break
                except RECOVERABLE_ERRORS:
                    # retries exhausted at stream-chunk granularity:
                    # the same ladder escalation as the round loop,
                    # re-attempting the chunk on the stepped-down
                    # config (overlap is idempotent, so the retry
                    # cannot re-advance the RNG)
                    if not ladder.can_step(cfg):
                        raise
                    met[obsdev.MET_LADDER_STEPS] += \
                        ladder.note_epoch(cfg, launch_failures=1)
            if "rng" not in nxt:
                overlap()     # e.g. every dispatch attempt failed
                #               fast; draw synchronously
            state = g.state
            if job.with_hists:
                hists = g.hists
            if job.with_ledger:
                ledger = g.ledger
            if job.flight_records:
                flight = g.flight
            if job.with_prov:
                prov = g.prov
            if job.with_slo:
                slo_block = g.slo
            stream_fallbacks += g.stream_fallback
            # the drain: per-epoch bookkeeping in epoch order, exactly
            # the round loop's sequence (digest -> metric fold ->
            # ladder note -> injector kill points), over the rows the
            # chunk accumulated in HBM
            with _spans.span(tracer, "stream.drain", "drain",
                             chunk=b - e0):
                for i in range(b - e0):
                    epoch = e0 + i
                    scr.tick(epoch, injector)
                    decisions += g.counts[i]
                    digest = _digest_update(
                        digest, plane.canon_results(g.epochs[i])
                        if plane is not None else g.epochs[i])
                    for r in g.epochs[i]:
                        if hasattr(r, "metrics") and \
                                r.metrics is not None:
                            met = obsdev.metrics_combine_np(
                                met, jax.device_get(r.metrics))
                    met[obsdev.MET_LADDER_STEPS] += ladder.note_epoch(
                        cfg, guard_trips=g.guard_trips[i])
                    if injector is not None:
                        injector.after_decisions(decisions)
            # the stream heartbeat: a drain-point instant the watchdog
            # reads as launch-cadence liveness (a fused chunk
            # legitimately runs for seconds with no dispatch span
            # completing -- docs/OBSERVABILITY.md)
            _spans.instant(tracer, "stream.heartbeat", "drain",
                           epoch=b)
            closed = None
            if slo_plane is not None:
                # b is a window boundary by construction: every chunk
                # ends on the ckpt_every grid (chunk_bounds), so the
                # stream loop rolls at exactly the round loop's points
                cid_of_slot = plane.slots.cid_of_slot \
                    if plane is not None else None
                slo_block, closed = slo_plane.roll(
                    slo_block, slo_w0, b, cid_of_slot=cid_of_slot,
                    depth=state.depth)
                slo_w0 = b
                slo_eval.observe_roll(closed)
            if ctl is not None:
                # b is a controller boundary by construction (= the
                # round loop's at_boundary grid): same collect ->
                # decide -> actuate sequence, before the save
                sig = ctl.collect(
                    b, state=state, met=met, slo_eval=slo_eval,
                    prov=prov,
                    planes=None if plane is None else [plane])
                fired = ctl.step(
                    b, sig, fault=None if injector is None
                    else injector.controller_point)
                if "compact" in fired and plane is not None:
                    state, ledger, slo_block, prov = _ctl_compact(
                        plane, state, ledger, slo_block, prov, b)
            if ckpt_dir is not None:
                # b is a checkpoint boundary by construction
                # (chunk_bounds); the persisted RNG state is rng_ckpt
                # -- the snapshot covering draws for epochs < b only
                with _spans.span(tracer, "supervisor.checkpoint_save",
                                 "checkpoint", epoch=b):
                    payload = _payload(job, state, rng_ckpt, met,
                                       digest, b, decisions,
                                       ladder.encode(), hists=hists,
                                       ledger=ledger, flight=flight,
                                       prov=prov, plane=plane,
                                       slo=None if slo_plane is None
                                       else (slo_block, slo_plane,
                                             slo_eval), ctl=ctl)

                    def save(payload=payload):
                        return ckpt_mod.save_pytree_rotating(
                            ckpt_dir, payload, keep=job.keep)

                    if injector is not None:
                        injector.around_save(b - 1, save)
                    else:
                        save()
                if tracer is not None:
                    tracer.drain_jsonl(job.span_log)
                _slo_log_flush(slo_plane, job.slo_log, closed)
            else:
                # bare/unsupervised runner: nothing ever replays,
                # per-chunk flushes are safe
                _slo_log_flush(slo_plane, job.slo_log, closed)
                if tracer is not None:
                    tracer.drain_jsonl(job.span_log)
            counts = nxt.get("counts")
            rng_ckpt = nxt["rng"]
    except BaseException:
        # the crash hook, as in the round loop: best-effort flight
        # dump, NO span flush (un-flushed spans describe epochs a
        # resume will replay)
        if job.flight_dump and flight is not None:
            try:
                n = obsflight.flight_dump(flight, job.flight_dump)
                print(f"# supervisor: dumped {n} flight records to "
                      f"{job.flight_dump}", file=sys.stderr)
            except Exception:
                pass
        raise
    finally:
        scr.close()

    if tracer is not None:
        tracer.drain_jsonl(job.span_log)
    return _build_result(job, state, digest, decisions, met, ladder,
                         scr.rebinds, resumed_from, hists, ledger,
                         flight, stream_fallbacks, plane,
                         slo_block, slo_plane, slo_eval, prov,
                         ctl=ctl)


def _draw_counts_mesh(rng: np.random.Generator, job: EpochJob,
                      epochs: int) -> np.ndarray:
    """RAW per-epoch per-shard Poisson draws ``int32[S, epochs, N]``
    (shard axis leading for the mesh launch).  Epoch-major draw order
    with ``(S, N)`` per epoch: at S=1 the generator consumes the
    IDENTICAL variate sequence as the stream loop's ``_draw_counts``
    (numpy fills C-order), which is what makes the S=1 mesh digest
    equal the stream digest including the arrival stream."""
    draws = np.stack([rng.poisson(job.arrival_lam,
                                  (job.n_shards, job.n))
                      .astype(np.int32) for _ in range(epochs)])
    return np.swapaxes(draws, 0, 1)


def _mesh_boundary(job: EpochJob, planes, state, ledger,
                   cd, cr, vd, vr, b: int, prov=None, pm=None,
                   up=None):
    """One mesh churn job's lifecycle boundary: every shard's plane
    applies its own due ops to its own slice (registrations routed by
    the placement map when one exists, else ``cid % n_shards``;
    per-shard SlotMaps), the counter plane's cd/cr (fill 0), held
    views (fill 1), and the provenance last_served watermark (fill 0
    = never served) ride each shard's grow/evict/compact transforms
    as boundary extras, and the stacked layout is forced back
    RECTANGULAR: one shard's grow-on-demand doubling grows every
    sibling to the max capacity before the restack.

    ``pm`` (placement != "static") runs the p2c ROUTING PASS first:
    every registration due at this boundary -- last boundary's
    deferrals first, then this cohort in ascending-cid order -- gets
    its shard assigned against the current per-shard backlog and the
    boundary's liveness row ``up`` BEFORE any plane filters its due
    ops.  A deferral finally placed re-enters as a pending op on its
    destination plane (its scripted event already fired), so nothing
    is lost across a both-choices-down boundary."""
    import jax
    import jax.numpy as jnp

    from ..parallel import mesh as mesh_mod

    S = job.n_shards
    if pm is not None:
        from ..lifecycle import churn as churn_mod

        deferred = pm.take_deferred()
        if job.churn.get("static") and b == 0:
            due = list(range(int(job.churn["total_ids"])))
        else:
            due = [int(e["cid"])
                   for e in churn_mod.events(job.churn, b,
                                             job.ckpt_every)
                   if e["op"] == "register"]
        cohort = [cid for cid in due if pm.shard_of(cid) < 0]
        if deferred or cohort:
            backlog = np.asarray(jax.device_get(state.depth),
                                 dtype=np.int64).sum(axis=-1)
            placed = pm.place_batch(deferred + cohort,
                                    backlog=backlog, up=up)
            for cid in placed:
                if cid in deferred:
                    # its scripted event fired at the earlier
                    # boundary; re-enter through the pending journal
                    r, w, l = churn_mod.init_qos(job.churn, cid)
                    planes[pm.shard_of(cid)].pending.append(
                        {"op": "register", "cid": cid, "r": r,
                         "w": w, "l": l, "apply_at": b})
    sts, leds, ctrs = [], [], []
    for s in range(S):
        st_s = mesh_mod.unstack_shard(state, s)
        led_s = None if ledger is None else ledger[s]
        extras = [(jnp.asarray(cd[s]), 0), (jnp.asarray(cr[s]), 0),
                  (jnp.asarray(vd[s]), 1), (jnp.asarray(vr[s]), 1)]
        if prov is not None:
            extras.append((prov.last_served[s], 0))
        st_s, led_s, extras = planes[s].boundary(
            st_s, b, job.ckpt_every, ledger=led_s, extras=extras)
        sts.append(st_s)
        leds.append(led_s)
        ctrs.append(extras)
    cap = max(int(st.capacity) for st in sts)
    for s in range(S):
        out = planes[s].ensure_capacity(cap, sts[s], ledger=leds[s],
                                        extras=ctrs[s])
        sts[s], leds[s] = out[0], out[1]
        ctrs[s] = out[-1]

    def restack(parts):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *parts)

    state = restack(sts)
    ledger = None if ledger is None else jnp.stack(leds)
    cd, cr, vd, vr = (jnp.stack([ctrs[s][j][0] for s in range(S)])
                      for j in range(4))
    if prov is not None:
        from ..obs import provenance as obsprov

        prov = obsprov.prov_from_arrays(
            prov.margin_hist, prov.scal,
            jnp.stack([ctrs[s][4][0] for s in range(S)]))
    return state, ledger, cd, cr, vd, vr, prov


def _mesh_migrate(job: EpochJob, pm, ctl, planes, state, ledger,
                  cd, cr, vd, vr, b: int, prov=None, up=None,
                  press=None):
    """The controller's ``migrate`` actuation (docs/LIFECYCLE.md
    "Placement and migration"): move up to ``migrate_max`` drained
    clients off the hottest live shard as the EXISTING digest-neutral
    lifecycle ops -- EVICT on the source (final ledger row folded
    into the departed report first), REGISTER on the destination with
    the carried counter views (cd/cr completions, vd/vr held views --
    the paper's delta/rho piggyback as handoff) and the provenance
    last_served watermark installed at the destination slot.

    Determinism/crash story: the trigger is journaled (a resumed run
    REPLAYS it), the destination draws come from the checkpointed
    placement RNG, and the candidate order is a pure function of the
    replayed boundary state -- so a SIGKILL at ANY stage of
    evict -> handoff -> register (the ``placement._migrate_hook``
    seam) replays the identical move list from the previous
    checkpoint.  Runs AFTER the controller boundary and BEFORE the
    boundary's checkpoint save, like every other actuation."""
    import jax
    import jax.numpy as jnp

    from ..lifecycle import placement as placement_mod
    from ..lifecycle.plane import (LC_EVICT, LC_NOP, _pad_len,
                                   apply_op_vector)
    from ..parallel import mesh as mesh_mod

    S = job.n_shards
    depth = np.asarray(jax.device_get(state.depth), dtype=np.int64)
    backlog = depth.sum(axis=-1)
    # source = hottest LIVE shard (a down shard has no pressure to
    # shed -- its in-chunk commits are masked -- and its host-side
    # rows stay put until it returns)
    eligible = np.asarray(
        [int(backlog[s]) if (up is None or bool(up[s])) else -1
         for s in range(S)], dtype=np.int64)
    src = int(np.argmax(eligible))
    if eligible[src] <= 0:
        # boundary-time depth is structurally zero on calendar
        # engines (deadline commits drain within the epoch): fall
        # back to the chunk's mid-epoch pressure peaks -- the same
        # replay-deterministic signal that armed the rule
        if press is None:
            return state, ledger, cd, cr, vd, vr, prov
        from ..obs import provenance as obsprov
        peaks = np.asarray(press, dtype=np.int64)[
            :, obsprov.PRESS_BACKLOG]
        eligible = np.asarray(
            [int(peaks[s]) if (up is None or bool(up[s])) else -1
             for s in range(S)], dtype=np.int64)
        src = int(np.argmax(eligible))
        if eligible[src] <= 0:
            return state, ledger, cd, cr, vd, vr, prov
    plane_src = planes[src]
    cd_src = np.asarray(jax.device_get(cd[src]), dtype=np.int64)
    pick = ctl.migrate_pick()
    keyed = []
    for cid in sorted(plane_src.slots.slot_of):
        slot = plane_src.slots.slot_of[cid]
        # only DRAINED clients move: there are no queued ops to
        # teleport, so the whole handoff is counter state + contract
        if depth[src, slot] != 0:
            continue
        served = int(cd_src[slot])
        if pick == "cold" and served == 0:
            # quiet-since-start movers -- the digest gate's provably
            # placement-equivalent class (ascending cid)
            keyed.append((0, cid))
        elif pick != "cold" and served > 0:
            # largest served demand first (cid breaks ties): the
            # clients whose future arrivals the move actually sheds
            keyed.append((-served, cid))
    moves = pm.plan_moves(b, src=src,
                          candidates=[cid for _k, cid in sorted(keyed)],
                          backlog=backlog, up=up,
                          max_moves=ctl.migrate_batch())
    if not moves:
        return state, ledger, cd, cr, vd, vr, prov

    sts = [mesh_mod.unstack_shard(state, s) for s in range(S)]
    leds = [None if ledger is None else ledger[s] for s in range(S)]
    ctrs = [[(jnp.asarray(cd[s]), 0), (jnp.asarray(cr[s]), 0),
             (jnp.asarray(vd[s]), 1), (jnp.asarray(vr[s]), 1)]
            + ([(prov.last_served[s], 0)] if prov is not None else [])
            for s in range(S)]

    # source half: read the carried riders BEFORE the rows reset,
    # fold the final ledger rows, release the slots, EVICT on device
    carried = {}
    evict_rows = []
    handoff = []
    for cid, dst in moves:
        out = plane_src.migrate_out(cid, leds[src])
        if out is None:
            continue
        slot, qos = out
        carried[cid] = [arr[slot] for arr, _fill in ctrs[src]]
        evict_rows.append((LC_EVICT, slot, 0, 0, 0, 0))
        handoff.append((cid, dst, qos))
    if evict_rows:
        pad = _pad_len(len(evict_rows))
        rows = evict_rows + [(LC_NOP, 0, 0, 0, 0, 0)] \
            * (pad - len(evict_rows))
        arr = np.asarray(rows, dtype=np.int64)
        sts[src] = apply_op_vector(sts[src], arr[:, 0], arr[:, 1],
                                   arr[:, 2], arr[:, 3], arr[:, 4],
                                   arr[:, 5])
        idx = jnp.asarray([r[1] for r in evict_rows])
        if leds[src] is not None:
            leds[src] = leds[src].at[idx].set(0)
        ctrs[src] = [(a.at[idx].set(f), f) for a, f in ctrs[src]]
    if placement_mod._migrate_hook is not None:
        placement_mod._migrate_hook("evicted")

    # destination half: REGISTER with the carried QoS contract
    reg_rows: dict = {s: [] for s in range(S)}
    for cid, dst, qos in handoff:
        reg_rows[dst] += planes[dst].migrate_in(cid, qos)
    if placement_mod._migrate_hook is not None:
        placement_mod._migrate_hook("handoff")

    # one rectangle: a destination's grow-on-demand forces every
    # sibling to the same capacity before the restack
    cap = max(max(int(p.slots.capacity) for p in planes),
              max(int(st.capacity) for st in sts))
    for s in range(S):
        out = planes[s].ensure_capacity(cap, sts[s], ledger=leds[s],
                                        extras=ctrs[s])
        sts[s], leds[s] = out[0], out[1]
        ctrs[s] = out[-1]
    for s in range(S):
        if not reg_rows[s]:
            continue
        rows = list(reg_rows[s])
        pad = _pad_len(len(rows))
        rows += [(LC_NOP, 0, 0, 0, 0, 0)] * (pad - len(rows))
        arr = np.asarray(rows, dtype=np.int64)
        sts[s] = apply_op_vector(sts[s], arr[:, 0], arr[:, 1],
                                 arr[:, 2], arr[:, 3], arr[:, 4],
                                 arr[:, 5])
    # install the carried riders at the destination slots: the
    # delta/rho completions and held views arrive WITH the client
    # (the piggyback-as-handoff), the last_served watermark keeps its
    # starvation clock honest across the move
    for cid, dst, _qos in handoff:
        slot_d = planes[dst].slots.slot_of[cid]
        ctrs[dst] = [(a.at[slot_d].set(v), f)
                     for (a, f), v in zip(ctrs[dst], carried[cid])]
    if placement_mod._migrate_hook is not None:
        placement_mod._migrate_hook("registered")

    state = jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
    ledger = None if ledger is None else jnp.stack(leds)
    cd, cr, vd, vr = (jnp.stack([ctrs[s][j][0] for s in range(S)])
                      for j in range(4))
    if prov is not None:
        from ..obs import provenance as obsprov

        prov = obsprov.prov_from_arrays(
            prov.margin_hist, prov.scal,
            jnp.stack([ctrs[s][4][0] for s in range(S)]))
    return state, ledger, cd, cr, vd, vr, prov


def _mesh_epochs(job: EpochJob, injector, ckpt_dir,
                 scr: _ScrapeCtl, base_cfg: dict, state, rng, met,
                 digest: bytes, start_epoch: int, decisions: int,
                 ladder, tracer, hists, ledger, flight, prov,
                 resumed_from, slo_block=None, slo_plane=None,
                 slo_eval=None, mesh_ctrs=None,
                 planes=None, ctl=None, pm=None) -> SupervisedResult:
    """The mesh serving loop (docs/ENGINE.md "Mesh serving"):
    ``n_shards`` full per-device engines advance a whole
    checkpoint-boundary chunk of epochs inside ONE ``shard_map``
    launch (``parallel.mesh.build_mesh_chunk`` -- the stream chunk's
    own epoch step, sharded), with the paper's delta/rho counter
    views exchanged through the [C]-sized psum on the global
    ``counter_sync_every`` epoch grid and the per-shard SLO window
    blocks merged in-graph through ``window_mesh_reduce`` into the
    ONE cluster-wide conformance table the SLO plane rolls.

    ``job.fault_plan`` (docs/ROBUSTNESS.md "Degraded-mode mesh")
    samples a deterministic ``FaultPlan`` over (epochs, n_shards) and
    compiles each chunk's slice INTO the fused launch as traced fault
    masks; a guard trip during a chaos chunk replays the same
    schedule on the host robust loop (``mesh_chaos_fallbacks``).
    ``planes`` (mesh churn) drives per-shard lifecycle boundaries at
    the chunk grid with the counter plane riding each shard's
    slot transforms; the chain digest hashes each shard's results
    through that shard's canonical slot->cid view, so the S>1
    dynamic==static gate holds.

    Crash-equivalence discipline: the chunk's raw draws are taken
    synchronously right before the launch and the checkpointed RNG
    state is the post-draw snapshot, so a resumed incarnation
    re-draws epochs >= the boundary bit-identically; the counter
    plane (per-shard completions + held views) rides the rotation
    checkpoints as ``mesh_*`` leaves, the fault plan is recomputed
    from its spec (pure host data).  The per-epoch drain bookkeeping
    (chain digest over the per-shard decision streams in shard order,
    metric fold, ladder notes, injector kill points) is the stream
    loop's, so at S=1 the two loops are bit-identical end to end."""
    import jax
    import jax.numpy as jnp

    from ..engine import stream as stream_mod
    from ..obs import device as obsdev
    from ..obs import spans as _spans
    from ..parallel import mesh as mesh_mod
    from .faults import parse_fault_spec, plan_chunk, plan_from_spec
    from .guarded import run_mesh_chunk_guarded

    n_dev = len(jax.devices())
    if job.n_shards > n_dev:
        raise ValueError(
            f"EpochJob(n_shards={job.n_shards}) needs that many "
            f"devices; this backend has {n_dev} (force a host mesh "
            f"with jax_num_cpu_devices / "
            f"--xla_force_host_platform_device_count)")
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_mod.make_mesh(job.n_shards)
    sharding = NamedSharding(mesh, P(mesh_mod.SERVER_AXIS))
    # the stacked [S, ...] state (built by _job_state or restored from
    # a checkpoint) gets its leaves split over the servers mesh axis
    state = jax.tree.map(lambda a: jax.device_put(a, sharding), state)
    cd, cr, vd, vr = mesh_ctrs
    plan = None
    if job.fault_plan is not None:
        plan = plan_from_spec(parse_fault_spec(job.fault_plan),
                              job.epochs, job.n_shards)
    mesh_fallbacks = 0
    mesh_chaos_fallbacks = 0
    do_ingest = job.arrival_lam > 0 or planes is not None
    slo_w0 = start_epoch
    # when the job's SLO plane is off, slo_block stays None and the
    # guarded runner builds its own throwaway window block per chunk
    # (the counter plane needs one; never checkpointed -- the diffs
    # are chunk-local, cd/cr are what persist)
    wblock = slo_block
    try:
        for e0, b in stream_mod.chunk_bounds(start_epoch, job.epochs,
                                             job.ckpt_every):
            scr.tick(e0, injector)
            # mesh churn: every shard's lifecycle boundary applies
            # BEFORE the chunk, on the chunk grid (the stream loop's
            # discipline); the counter plane follows each shard's
            # slot transforms as boundary extras
            up_row = None if plan is None \
                else plan.up[min(e0, plan.up.shape[0] - 1)]
            if planes is not None:
                with _spans.span(tracer, "lifecycle.boundary",
                                 "host_prep", epoch=e0):
                    state, ledger, cd, cr, vd, vr, prov = \
                        _mesh_boundary(job, planes, state, ledger,
                                       cd, cr, vd, vr, e0, prov,
                                       pm=pm, up=up_row)
            counts = None
            if do_ingest:
                with _spans.span(tracer, "mesh.pregen", "host_prep"):
                    if planes is not None:
                        # ONE id-space draw per epoch for the whole
                        # cluster (identical RNG consumption in the
                        # dynamic run and its static variant), mapped
                        # onto each shard's POST-boundary slot layout
                        raw = _draw_counts_churn(rng, job.churn,
                                                 e0, b)
                        counts = np.stack(
                            [planes[s].map_counts(raw)
                             for s in range(job.n_shards)])
                    else:
                        counts = _draw_counts_mesh(rng, job, b - e0)
                    if ctl is not None:
                        # whole-chunk clamp under the chunk-start knob
                        # (the stream loop's discipline) -- applied
                        # AFTER the draws, so RNG consumption never
                        # depends on the controller
                        counts = ctl.clamp_counts(counts, job.waves)
            rng_ckpt = _rng_state_array(rng)
            faults = plan_chunk(plan, e0, b) \
                if plan is not None else None
            while True:
                cfg = ladder.apply(ctl.overlay(base_cfg)
                                   if ctl is not None else base_cfg)
                try:
                    g = run_mesh_chunk_guarded(
                        state, cd, cr, vd, vr, e0, counts, mesh=mesh,
                        engine=job.engine, epochs=b - e0, m=job.m,
                        k=job.k, chain_depth=job.chain_depth,
                        dt_epoch_ns=job.dt_epoch_ns, waves=job.waves,
                        with_metrics=True,
                        select_impl=cfg["select_impl"],
                        tag_width=cfg["tag_width"],
                        calendar_impl=cfg["calendar_impl"],
                        ladder_levels=job.ladder_levels,
                        wheel_kernel=job.wheel_kernel,
                        counter_sync_every=ctl.knob_sync()
                        if ctl is not None
                        else job.counter_sync_every,
                        with_pressure=ctl is not None,
                        hists=hists, ledger=ledger, slo=wblock,
                        prov=prov, flight=flight, faults=faults,
                        tracer=tracer)
                    break
                except RECOVERABLE_ERRORS:
                    if not ladder.can_step(cfg):
                        raise
                    met[obsdev.MET_LADDER_STEPS] += \
                        ladder.note_epoch(cfg, launch_failures=1)
            state, cd, cr, vd, vr = g.state, g.cd, g.cr, \
                g.view_d, g.view_r
            if job.with_hists:
                hists = g.hists
            if job.with_ledger:
                ledger = g.ledger
            if job.with_prov:
                prov = g.prov
            if job.flight_records:
                flight = g.flight
            if job.with_slo:
                slo_block = g.slo
                wblock = g.slo
            mesh_fallbacks += g.mesh_fallback
            if plan is not None:
                # a chaos chunk that degraded to the host robust loop
                # -- the fallback carried the identical fault
                # schedule, so the run stays on-plan, just slower
                mesh_chaos_fallbacks += g.mesh_fallback
            # the drain: per-epoch bookkeeping in epoch order, the
            # stream loop's exact sequence; the chain digest hashes
            # every shard's decision stream in shard order per epoch
            # (a churn job hashes each shard's CANONICAL slot->cid
            # view through that shard's own plane)
            with _spans.span(tracer, "mesh.drain", "drain",
                             chunk=b - e0, shards=job.n_shards):
                for i in range(b - e0):
                    epoch = e0 + i
                    scr.tick(epoch, injector)
                    decisions += g.counts[i]
                    if planes is not None:
                        flat = tuple(
                            r for s, grp in enumerate(g.epochs[i])
                            for r in planes[s].canon_results(grp))
                    else:
                        flat = tuple(r for grp in g.epochs[i]
                                     for r in grp)
                    digest = _digest_update(digest, flat)
                    for r in flat:
                        if hasattr(r, "metrics") and \
                                r.metrics is not None:
                            met = obsdev.metrics_combine_np(
                                met, jax.device_get(r.metrics))
                    met[obsdev.MET_LADDER_STEPS] += ladder.note_epoch(
                        cfg, guard_trips=g.guard_trips[i])
                    if injector is not None:
                        injector.after_decisions(decisions)
            _spans.instant(tracer, "mesh.heartbeat", "drain",
                           epoch=b)
            closed = None
            if slo_plane is not None:
                # roll the CLUSTER-WIDE merged table (the in-graph
                # window_mesh_reduce output); the fresh stamped block
                # re-broadcasts to every shard.  Backlog for the
                # starvation predicate is the cluster total (at S=1:
                # exactly the stream loop's per-shard depth).
                depth_sum = jnp.sum(state.depth.astype(jnp.int64),
                                    axis=0)
                merged, closed = slo_plane.roll(
                    jnp.asarray(g.slo_merged), slo_w0, b,
                    depth=depth_sum)
                slo_w0 = b
                slo_eval.observe_roll(closed)
                slo_block = mesh_mod.stack_shards(merged,
                                                  job.n_shards)
                wblock = slo_block
            if ctl is not None:
                # cluster-level controller boundary: signals aggregate
                # over every shard (backlog = cluster depth total,
                # press_backlog = hottest shard's total).  A fired
                # ``compact`` journals + counts as migration-eligible
                # only; a fired ``migrate`` (placement != "static")
                # ACTUATES -- _mesh_migrate moves drained clients off
                # the hottest shard as digest-neutral EVICT/REGISTER
                # handoffs, BEFORE this boundary's checkpoint save so
                # a replayed trigger re-moves the replayed state
                # deterministically (staleness / ladder / clamp knobs
                # actuate exactly as on the other loops).
                if g.press is not None and scr.scrape is not None:
                    # live placement signal: the chunk's per-shard
                    # mid-epoch peaks on the dmclock_shard_pressure_*
                    # gauges (best-effort host telemetry)
                    try:
                        from ..obs import provenance as obsprov
                        obsprov.publish_shard_pressure(
                            scr.scrape.registry, g.press)
                    except Exception:
                        pass
                sig = ctl.collect(b, state=state, met=met,
                                  slo_eval=slo_eval, prov=prov,
                                  planes=planes, press=g.press)
                fired = ctl.step(b, sig,
                                 fault=None if injector is None
                                 else injector.controller_point)
                if "migrate" in fired and pm is not None:
                    with _spans.span(tracer, "lifecycle.migrate",
                                     "host_prep", epoch=b):
                        up_b = None if plan is None \
                            else plan.up[min(b, plan.up.shape[0] - 1)]
                        state, ledger, cd, cr, vd, vr, prov = \
                            _mesh_migrate(job, pm, ctl, planes,
                                          state, ledger, cd, cr,
                                          vd, vr, b, prov=prov,
                                          up=up_b, press=g.press)
            if ckpt_dir is not None:
                with _spans.span(tracer, "supervisor.checkpoint_save",
                                 "checkpoint", epoch=b):
                    payload = _payload(job, state, rng_ckpt, met,
                                       digest, b, decisions,
                                       ladder.encode(), hists=hists,
                                       ledger=ledger, prov=prov,
                                       flight=flight, plane=planes,
                                       mesh=(cd, cr, vd, vr),
                                       slo=None if slo_plane is None
                                       else (slo_block, slo_plane,
                                             slo_eval), ctl=ctl,
                                       pm=pm)

                    def save(payload=payload):
                        return ckpt_mod.save_pytree_rotating(
                            ckpt_dir, payload, keep=job.keep)

                    if injector is not None:
                        injector.around_save(b - 1, save)
                    else:
                        save()
                if tracer is not None:
                    tracer.drain_jsonl(job.span_log)
                _slo_log_flush(slo_plane, job.slo_log, closed)
            else:
                _slo_log_flush(slo_plane, job.slo_log, closed)
                if tracer is not None:
                    tracer.drain_jsonl(job.span_log)
    except BaseException:
        # the crash hook, as in the round/stream loops: best-effort
        # per-shard flight dump (shard column added), NO span flush
        if job.flight_dump and flight is not None:
            try:
                from ..obs import flight as obsflight
                n = obsflight.flight_dump_any(flight, job.flight_dump)
                print(f"# supervisor: dumped {n} flight records to "
                      f"{job.flight_dump}", file=sys.stderr)
            except Exception:
                pass
        raise
    finally:
        scr.close()

    if tracer is not None:
        tracer.drain_jsonl(job.span_log)
    return _build_result(job, state, digest, decisions, met, ladder,
                         scr.rebinds, resumed_from, hists, ledger,
                         flight, 0, planes, slo_block, slo_plane,
                         slo_eval, prov,
                         mesh=(cd, cr, vd, vr),
                         mesh_fallbacks=mesh_fallbacks,
                         mesh_chaos_fallbacks=mesh_chaos_fallbacks,
                         ctl=ctl, pm=pm)


def _healthz_ok(scrape, timeout_s: float = 2.0) -> bool:
    """One-shot liveness probe of a scrape endpoint's ``/healthz``
    (obs.registry.MetricsHTTPServer) -- what a restarted incarnation
    polls after rebinding its port to confirm the endpoint actually
    serves again."""
    import urllib.request

    try:
        with urllib.request.urlopen(scrape.healthz_url,
                                    timeout=timeout_s) as resp:
            return resp.status == 200 \
                and b"ok" in resp.read()
    except Exception:
        return False


def run_job(job: EpochJob) -> SupervisedResult:
    """The bare runner: the uninterrupted, unsupervised reference.
    The zero-host-fault gate pins ``run_supervised(job, wd,
    zero_host_plan())`` bit-identical to this."""
    return _job_loop(job, None, None)


# ----------------------------------------------------------------------
# the supervisor
# ----------------------------------------------------------------------

JOB_FILE = "job.json"
RESULT_FILE = "result.json"
RESUME_LOG = "resume.log"


class _ChildKilled(RuntimeError):
    """Spawn-mode child died (signal or nonzero exit) before writing
    its result."""


# what the restart loop treats as "the runner died": plan kills
# (trampoline HostKill, spawn child death) AND a recoverable device/
# transport error that survived the guarded runner's bounded retries
# and the ladder -- in both modes that run is gone, but an intact
# rotation checkpoint remains to resume from.  Genuine caller bugs
# (ValueError, plain RuntimeError) still surface immediately in
# trampoline mode.
_RESTART_ERRORS = (HostKill, _ChildKilled) + RECOVERABLE_ERRORS


def run_supervised(job: EpochJob, workdir,
                   plan: Optional[HostFaultPlan] = None, *,
                   mode: str = "trampoline", max_restarts: int = 8,
                   backoff_base_s: float = 0.01, backoff_max_s: float = 1.0,
                   sleep: Callable[[float], None] = _time.sleep
                   ) -> SupervisedResult:
    """Run ``job`` to completion under the supervisor, injecting
    ``plan`` (None/empty = no host faults), restarting a killed job
    with bounded exponential backoff until it completes or
    ``max_restarts`` is exhausted (:class:`SupervisorGaveUp`).

    ``mode="trampoline"`` restarts in-process (plan kills raise
    :class:`HostKill`; fast, what the test matrix uses);
    ``mode="spawn"`` runs each incarnation as a child interpreter and
    plan kills are REAL ``SIGKILL`` -- the CI crash smoke's mode.
    ``workdir`` must be fresh per logical run (it holds the rotation
    checkpoints, the fired-points journal, and -- in spawn mode --
    the job/result files)."""
    assert mode in ("trampoline", "spawn"), mode
    workdir = os.fspath(workdir)
    os.makedirs(workdir, exist_ok=True)
    restarts = 0
    while True:
        try:
            if mode == "trampoline":
                injector = HostFaultInjector(plan, workdir,
                                             kill_mode="raise")
                result = _job_loop(job, workdir, injector)
            else:
                result = _spawn_once(job, workdir, plan)
            break
        except _RESTART_ERRORS as e:
            restarts += 1
            if restarts > max_restarts:
                raise SupervisorGaveUp(
                    f"{restarts - 1} restarts exhausted "
                    f"(last kill: {e})") from e
            sleep(min(backoff_base_s * (2.0 ** (restarts - 1)),
                      backoff_max_s))
    from ..obs import device as obsdev

    met = np.asarray(result.metrics, dtype=np.int64).copy()
    # the resume row counts restarts that restored a snapshot (the
    # durable journal every incarnation appends to), NOT raw restart
    # attempts: a replay-from-scratch restart pays a full recompute
    # and must read as zero resumes
    resumes = 0
    resume_log = os.path.join(workdir, RESUME_LOG)
    if os.path.exists(resume_log):
        with open(resume_log) as fh:
            resumes = sum(1 for ln in fh if ln.strip())
    met[obsdev.MET_SUPERVISOR_RESUMES] = resumes
    return result._replace(metrics=met, restarts=restarts)


def _assert_chip_free() -> None:
    """A chip belongs to one process: a spawn-mode parent that holds
    an accelerator backend would leave its child failing or hanging
    on the device.  The parent itself never touches a backend (it
    only reads the child's JSON result), so a caller that already
    holds the chip must run the job in-process (trampoline mode)."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            "run_supervised(mode='spawn') from a process that holds "
            f"the {jax.default_backend()} backend: the child could not "
            "reach the chip; use mode='trampoline' here")


def _spawn_once(job: EpochJob, workdir: str,
                plan: Optional[HostFaultPlan]) -> SupervisedResult:
    """One child-process incarnation: write the job file, run
    ``python -m dmclock_tpu.robust.supervisor <workdir>``, read the
    result back.  A SIGKILLed child leaves no result file and raises
    :class:`_ChildKilled` for the restart loop."""
    _assert_chip_free()
    job_path = os.path.join(workdir, JOB_FILE)
    res_path = os.path.join(workdir, RESULT_FILE)
    if os.path.exists(res_path):
        os.unlink(res_path)
    with open(job_path, "w") as fh:
        json.dump({"job": job.to_json(),
                   "plan": plan_to_json(plan)}, fh)
    proc = subprocess.run(
        [sys.executable, "-m", "dmclock_tpu.robust.supervisor",
         workdir], cwd=os.getcwd(), env=os.environ.copy())
    if proc.returncode != 0 or not os.path.exists(res_path):
        raise _ChildKilled(f"child exited {proc.returncode} "
                           f"({describe_host(plan)})")
    with open(res_path) as fh:
        obj = json.load(fh)

    def arr(key):
        v = obj.get(key)
        return None if v is None else np.asarray(v, dtype=np.int64)

    def arr2(key, cols):
        v = obj.get(key)
        if v is None:
            return None
        a = np.asarray(v, dtype=np.int64)
        # an empty list round-trips as shape (0,): restore the column
        # layout.  A non-empty block keeps its own rank -- a mesh
        # job's slo_window is the STACKED [S, N, cols] layout and a
        # forced reshape would flatten the shard axis.
        return a.reshape(-1, cols) if a.size == 0 or a.ndim < 2 else a

    from ..obs import slo as obsslo

    return SupervisedResult(
        digest=obj["digest"], state_digest=obj["state_digest"],
        decisions=int(obj["decisions"]), epochs=int(obj["epochs"]),
        metrics=np.asarray(obj["metrics"], dtype=np.int64),
        restarts=0, ladder_steps=obj["ladder_steps"],
        scrape_rebinds=int(obj["scrape_rebinds"]),
        resumed_from=obj.get("resumed_from"),
        hists=arr("hists"), ledger=arr("ledger"),
        flight_buf=arr("flight_buf"),
        flight_seq=int(obj.get("flight_seq", 0)),
        stream_fallbacks=int(obj.get("stream_fallbacks", 0)),
        lifecycle=obj.get("lifecycle"),
        slo_window=arr2("slo_window", obsslo.W_FIELDS),
        slo_ring=arr2("slo_ring", obsslo.RING_COLS),
        slo_cepoch=arr2("slo_cepoch", 2),
        slo=obj.get("slo"),
        prov_margin_hist=arr("prov_margin_hist"),
        prov_scal=arr("prov_scal"),
        prov_last_served=arr("prov_last_served"),
        mesh_counters=arr("mesh_counters"),
        mesh_views=arr("mesh_views"),
        mesh_fallbacks=int(obj.get("mesh_fallbacks", 0)),
        mesh_chaos_fallbacks=int(obj.get("mesh_chaos_fallbacks", 0)),
        controller_decisions=int(obj.get("controller_decisions", 0)),
        controller_replays=int(obj.get("controller_replays", 0)),
        controller_knobs=obj.get("controller_knobs"),
        controller_trajectory=obj.get("controller_trajectory"))


def _child_main(workdir: str) -> int:
    """Spawn-mode child entry: run one incarnation of the job in
    ``<workdir>/job.json`` with REAL SIGKILL plan points, then write
    the result atomically.  The platform comes from the environment
    the parent passed down (``JAX_PLATFORMS``)."""
    import jax

    from ..utils.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()

    with open(os.path.join(workdir, JOB_FILE)) as fh:
        obj = json.load(fh)
    job = EpochJob.from_json(obj["job"])
    plan = plan_from_json(obj.get("plan", {}))
    injector = HostFaultInjector(plan, workdir, kill_mode="sigkill")
    result = _job_loop(job, workdir, injector)
    res_path = os.path.join(workdir, RESULT_FILE)
    tmp = res_path + f".tmp.{os.getpid()}"
    def lst(v):
        return None if v is None else np.asarray(v).tolist()

    with open(tmp, "w") as fh:
        json.dump({"digest": result.digest,
                   "state_digest": result.state_digest,
                   "decisions": result.decisions,
                   "epochs": result.epochs,
                   "metrics": np.asarray(result.metrics).tolist(),
                   "ladder_steps": result.ladder_steps,
                   "scrape_rebinds": result.scrape_rebinds,
                   "resumed_from": result.resumed_from,
                   "hists": lst(result.hists),
                   "ledger": lst(result.ledger),
                   "flight_buf": lst(result.flight_buf),
                   "flight_seq": result.flight_seq,
                   "stream_fallbacks": result.stream_fallbacks,
                   "lifecycle": result.lifecycle,
                   "slo_window": lst(result.slo_window),
                   "slo_ring": lst(result.slo_ring),
                   "slo_cepoch": lst(result.slo_cepoch),
                   "slo": result.slo,
                   "prov_margin_hist": lst(result.prov_margin_hist),
                   "prov_scal": lst(result.prov_scal),
                   "prov_last_served":
                       lst(result.prov_last_served),
                   "mesh_counters": lst(result.mesh_counters),
                   "mesh_views": lst(result.mesh_views),
                   "mesh_fallbacks": result.mesh_fallbacks,
                   "mesh_chaos_fallbacks":
                       result.mesh_chaos_fallbacks,
                   "controller_decisions":
                       result.controller_decisions,
                   "controller_replays": result.controller_replays,
                   "controller_knobs": result.controller_knobs,
                   "controller_trajectory":
                       result.controller_trajectory}, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, res_path)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv[1]))
