"""On-device scheduling metrics: one small int64 vector, zero extra
round trips.

The epoch scans (``engine.fastpath``) and the serial batch runner
(``engine.kernels.engine_run``) already read back per-batch commit
counts; the metrics vector rides in the same scan carry and the same
fetch.  Accumulation is pure reductions over arrays the kernels
already materialize (decision phases, depths, guard bits), gated on a
STATIC ``with_metrics`` flag so the decision stream -- and, with the
flag off, the compiled program -- is bit-identical to the pre-metrics
kernels (pinned by ``tests/test_obs.py``).

Vector layout (int64[NUM_METRICS]); counters accumulate by addition,
high-water marks by ``maximum``.

The scalar vector is the cheapest tier of the device telemetry plane;
``obs.histograms`` (log2-bucketed QoS distributions + the per-client
conformance ledger) and ``obs.flight`` (the HBM flight recorder) ride
the same scan carries under the same bit-identical-decisions contract
and merge through the same psum/pmax collective path
(``metrics_mesh_reduce`` / ``hist_mesh_reduce`` /
``ledger_mesh_reduce``).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# -- indices -----------------------------------------------------------
MET_DECISIONS = 0       # decisions committed (all phases)
MET_RESV = 1            # constraint-phase (reservation) decisions
MET_PROP = 2            # weight-phase (priority) decisions
MET_LIMIT_BREAK = 3     # AtLimit::Allow limit-break serves
MET_STALLS = 4          # limit-capped stalls: batches/steps that
#                         committed nothing while work was queued
MET_RING_HWM = 5        # ring occupancy high-water mark (max depth)
MET_GUARD_TRIPS = 6     # rebase-guard trips (fastpath fallbacks)
MET_INGEST_DROPS = 7    # arrivals dropped by the admission clamp
MET_REBASE_FALLBACKS = 8  # int32 tag-rebase window trips (epoch ran
#                           out of the +-2^31 ns window; the batch
#                           committed nothing and the caller must rerun
#                           it on the int64 tag path)
MET_SERVER_DROPOUTS = 9   # cluster fault layer: up -> down transitions
#                           (robust.cluster; docs/ROBUSTNESS.md)
MET_TRACKER_RESYNCS = 10  # cluster fault layer: down -> up restarts
#                           that re-synced TrackerState marks from the
#                           monotone global counters
MET_FAULTS_INJECTED = 11  # total injected fault events (dropouts,
#                           restarts, delayed counters, duplicated
#                           completions, nonzero clock skew) -- every
#                           FaultPlan perturbation is visible here
MET_CAL_LADDER_LEVELS = 12  # bucketed calendar: ladder levels that
#                             committed > 0 decisions (summed over
#                             batches; minstop batches count as one
#                             level when they commit)
MET_CAL_LADDER_BASE = 13  # bucketed calendar: decisions the FIRST
#                           ladder level committed -- the minstop-
#                           equivalent share, so (decisions_total -
#                           this) is what the ladder bought per launch
MET_CAL_LADDER_FALLBACKS = 14  # bucketed calendar: batches whose
#                                ladder stalled (a level committed 0
#                                with candidates present -- the
#                                serial-fallback analog; remaining
#                                levels of that batch are wasted)
MET_LADDER_STEPS = 15     # degradation-ladder step-downs taken
#                           (robust.guarded.DegradationLadder:
#                           bucketed->minstop, radix->sort,
#                           tag32->int64; docs/ROBUSTNESS.md).  Reads
#                           zero when the ladder is disabled or never
#                           engaged (the zero-cost-when-off gate).
MET_SUPERVISOR_RESUMES = 16  # supervisor restarts that resumed from a
#                              rotation checkpoint (robust.supervisor).
#                              A resume_* row: crash-equivalence
#                              compares metric totals MODULO this row
#                              (an interrupted run legitimately differs
#                              here and nowhere else).
MET_WHEEL_OCC_HWM = 17    # wheel calendar: bucket-occupancy high-water
#                           mark (max clients sharing one (class,
#                           bucket) cell -- discrimination health of
#                           the wheel geometry; an hwm row)
MET_WHEEL_RESLOTS = 18    # wheel calendar: in-place bucket re-slots
#                           (clients whose (class, key) moved between
#                           ladder levels / API adjust events -- the
#                           O(moved) work the wheel does instead of a
#                           full O(N) re-measure)
MET_PALLAS_FALLBACKS = 19  # retired, always 0: a wheel_kernel=
#                            "pallas" request that cannot run raises
#                            (PR 21) instead of running the XLA
#                            reference; the row keeps the layout
NUM_METRICS = 20

METRIC_NAMES = (
    "decisions_total", "decisions_reservation", "decisions_priority",
    "decisions_limit_break", "limit_stalls", "ring_occupancy_hwm",
    "rebase_guard_trips", "ingest_drops", "rebase_fallbacks",
    "server_dropouts", "tracker_resyncs", "faults_injected",
    "calendar_ladder_levels_used", "calendar_ladder_base_decisions",
    "calendar_ladder_fallbacks", "degradation_ladder_steps",
    "supervisor_resumes", "wheel_bucket_occupancy_hwm",
    "wheel_reslots_total", "wheel_pallas_fallbacks",
)

# rows an interrupted-and-resumed run may legitimately grow relative
# to its uninterrupted reference (the "modulo resume_* rows" clause of
# the crash-equivalence digest gate; robust.supervisor)
RESUME_ROWS = (MET_SUPERVISOR_RESUMES,)

# the max-accumulated rows (everything else adds).  The mask is a
# HOST (numpy) constant on purpose: this module is imported lazily
# from inside jitted code paths, and a module-level jnp array built
# under an active trace would leak a tracer into the global --
# jnp.where folds the numpy constant in at trace time either way.
_HWM_ROWS = (MET_RING_HWM, MET_WHEEL_OCC_HWM)
_HWM_MASK = np.zeros((NUM_METRICS,), dtype=bool)
for _i in _HWM_ROWS:
    _HWM_MASK[_i] = True


def metrics_zero() -> jnp.ndarray:
    return jnp.zeros((NUM_METRICS,), dtype=jnp.int64)


def metrics_combine(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Merge two metric vectors (counters add, high-water marks max) --
    the device-side analog of ``ProfileCombiner``.  Associative and
    commutative, so shards/epochs merge in any order (and through a
    psum-of-counters + pmax-of-hwm on a mesh)."""
    return jnp.where(_HWM_MASK, jnp.maximum(a, b), a + b)


def metrics_delta(*, decisions=0, resv=0, prop=0, limit_break=0,
                  stalls=0, ring_hwm=0, guard_trips=0,
                  ingest_drops=0, rebase_fallbacks=0,
                  server_dropouts=0, tracker_resyncs=0,
                  faults_injected=0, cal_ladder_levels_used=0,
                  cal_ladder_base_decisions=0,
                  cal_ladder_fallbacks=0, ladder_steps=0,
                  supervisor_resumes=0, wheel_occ_hwm=0,
                  wheel_reslots=0) -> jnp.ndarray:
    """Build a one-batch delta vector from scalar contributions."""
    rows = [decisions, resv, prop, limit_break, stalls, ring_hwm,
            guard_trips, ingest_drops, rebase_fallbacks,
            server_dropouts, tracker_resyncs, faults_injected,
            cal_ladder_levels_used, cal_ladder_base_decisions,
            cal_ladder_fallbacks, ladder_steps, supervisor_resumes,
            wheel_occ_hwm, wheel_reslots, 0]
    return jnp.stack([jnp.asarray(r, dtype=jnp.int64) for r in rows])


def pmax_i64(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """``lax.pmax`` for int64: TPU all-reduces lower only SUM for
    64-bit integers, so the max runs as two int32 max collectives --
    the signed high words, then the bias-flipped low words among the
    shards that tie the max high word ((hi signed, lo unsigned) order
    IS the int64 order; the kernels_pallas wheel-scan split)."""
    from jax import lax

    hi = (x >> 32).astype(jnp.int32)
    lo = ((x & jnp.int64(0xFFFFFFFF))
          ^ jnp.int64(0x80000000)).astype(jnp.int32)
    mhi = lax.pmax(hi, axis_name)
    mlo = lax.pmax(jnp.where(hi == mhi, lo, jnp.int32(-(1 << 31))),
                   axis_name)
    return (mhi.astype(jnp.int64) << 32) | \
        (mlo.astype(jnp.int64) + jnp.int64(1 << 31))


def metrics_mesh_reduce(vec: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """In-graph mesh merge of per-shard metric vectors: counter rows
    ``psum``, high-water-mark rows ``pmax`` -- the collective form of
    :func:`metrics_combine` (associative + commutative, so the mesh
    order cannot matter).  Call inside ``shard_map`` on the per-shard
    vector; the result is replicated across the axis, so cluster
    totals need no host-side gather (the ROADMAP healthy-path item)."""
    from jax import lax

    return jnp.where(_HWM_MASK, pmax_i64(vec, axis_name),
                     lax.psum(vec, axis_name))


def metrics_combine_axis(mat: jnp.ndarray) -> jnp.ndarray:
    """Reduce a stacked [S, NUM_METRICS] matrix along its leading axis
    with the vector's merge semantics (counters add, hwm max) -- the
    local-shard half of a mesh merge (vmapped servers within a shard
    reduce here, then :func:`metrics_mesh_reduce` crosses the mesh)."""
    return jnp.where(_HWM_MASK, jnp.max(mat, axis=0),
                     jnp.sum(mat, axis=0))


def admission_clamp(counts: jnp.ndarray, headroom: jnp.ndarray):
    """Clamp per-client arrival counts to ring headroom (the AtLimit
    Reject/EAGAIN analog the sustained bench applies before
    ``ingest_superwave``), returning ``(clamped, dropped_total)`` so
    the drop count feeds MET_INGEST_DROPS instead of vanishing."""
    clamped = jnp.minimum(counts, headroom)
    dropped = jnp.sum((counts - clamped).astype(jnp.int64))
    return clamped, dropped


def metrics_combine_np(acc, *vecs):
    """Host-side mirror of :func:`metrics_combine` over numpy vectors
    (bench.py merges fetched per-chain vectors with this).  Derives the
    max rows from the same ``_HWM_ROWS`` as the device mask, so the two
    merges cannot silently diverge."""
    import numpy as np

    acc = np.asarray(acc, dtype=np.int64)
    hwm = np.isin(np.arange(acc.size), _HWM_ROWS)
    for v in vecs:
        v = np.asarray(v)
        acc = np.where(hwm, np.maximum(acc, v), acc + v)
    return acc


def metrics_dict(vec) -> dict:
    """Name the rows of a fetched metrics vector (host side)."""
    import numpy as np

    v = np.asarray(vec).reshape(-1)
    return {name: int(v[i]) for i, name in enumerate(METRIC_NAMES)}


FAULT_FAMILIES = (
    ("dmclock_fault_server_dropouts_total", MET_SERVER_DROPOUTS,
     "up -> down shard transitions injected by the fault plan "
     "(docs/ROBUSTNESS.md 'Degraded-mode mesh')"),
    ("dmclock_fault_tracker_resyncs_total", MET_TRACKER_RESYNCS,
     "down -> up restarts that re-synced the shard's held counter "
     "view / tracker marks from the monotone global counters"),
    ("dmclock_fault_injected_total", MET_FAULTS_INJECTED,
     "total injected fault events (dropouts, restarts, delayed "
     "counters, duplicated completions, nonzero clock skew)"),
)


def publish_shard_faults(registry, per_shard, labels=None) -> None:
    """Register the ``shard``-labelled ``dmclock_fault_*`` families
    from a ``[S, NUM_METRICS]`` per-shard metric matrix (or a
    ``[S, 3]`` dropouts/resyncs/injected matrix, e.g. the
    ``robust.faults.plan_shard_events`` oracle stacked column-wise):
    one gauge per family per shard plus a ``shard="all"`` total --
    the degraded-mode mesh's scrape surface next to the
    ``dmclock_slo_window_*`` / ``dmclock_shard_pressure_*``
    precedents."""
    import numpy as np

    mat = np.asarray(per_shard, dtype=np.int64)
    assert mat.ndim == 2, mat.shape
    cols = {name: (row if mat.shape[1] == NUM_METRICS else j)
            for j, (name, row, _help) in enumerate(FAULT_FAMILIES)}
    for name, _row, help_text in FAULT_FAMILIES:
        col = cols[name]
        for s in range(mat.shape[0]):
            registry.gauge(
                name, help_text,
                labels={**(labels or {}), "shard": str(s)}
            ).set(int(mat[s, col]))
        registry.gauge(
            name, help_text,
            labels={**(labels or {}), "shard": "all"}
        ).set(int(mat[:, col].sum()))


def publish(registry, vec, prefix: str = "dmclock_engine",
            labels=None) -> None:
    """Fold a fetched metrics vector into a host ``MetricsRegistry``:
    counter rows become counters (the vector is itself cumulative per
    run, so the registry gauge semantics fit better -- publish uses
    gauges for everything, with the hwm documented as a max)."""
    for name, value in metrics_dict(vec).items():
        registry.gauge(f"{prefix}_{name}",
                       "on-device scheduling metric (see "
                       "docs/OBSERVABILITY.md)",
                       labels=labels).set(value)
