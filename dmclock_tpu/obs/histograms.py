"""On-device QoS telemetry: log2-bucketed histograms + per-client
conformance ledger.

PR-1's metrics vector gives 17 scalar counters; the paper's whole
point is per-client QoS *distributions* -- reservation met, limit
respected, proportional share delivered -- and until now percentiles
only existed as a host-computed sim table after the run.  This module
keeps the distributions IN the data path (RackSched's thesis applied
to our stack): both structures ride the epoch-scan carries next to the
``obs.device`` metrics vector, are accumulated from pure reductions
over arrays the kernels already materialize, and are fetched with the
existing readback -- zero extra round trips, and the decision stream
is bit-identical with telemetry on or off (pinned by
``tests/test_telemetry.py``).

**Histograms** (``int64[NUM_HISTS, NUM_BUCKETS + 1]``): four
families x 48 log2 buckets + one value-sum column (so Prometheus
``_sum``/``_count`` are exact).  Bucket 0 holds values <= 0; bucket i
(1..46) holds ``2^(i-1) <= v < 2^i``; bucket 47 holds ``v >= 2^46``.
Bucketing is exact integer comparison against powers of two -- no
float log2, so the same value lands in the same bucket on every
backend.  Merge is elementwise add (pure counters), so epochs/shards
combine in any order and :func:`hist_mesh_reduce` is a plain ``psum``
-- the same collective path as ``obs.device.metrics_mesh_reduce``.

**Ledger** (``int64[N, LED_COLS]``): per-client served ops,
reservation-phase ops, limit-break serves, reservation-tardiness sum
and max.  Counter columns add, the max column maxes
(:func:`ledger_combine`), so the same fold/merge algebra as the
metrics vector applies.  The ledger is device truth: the sims' and
bench's host-side conformance recomputation cross-checks against it
instead of being the only record.

Observation semantics (documented here because the batch engines emit
sets, not streams -- docs/OBSERVABILITY.md has the full table):

- ``decision_latency_ns``: per committed weight-phase ENTRY,
  ``max(now - effective proportion tag, 0)`` -- how far behind its
  virtual-time tag the serve landed (0 = served at/ahead of tag).
- ``resv_tardiness_ns``: per committed constraint-phase ENTRY,
  ``max(now - reservation tag, 0)`` -- lateness past the reservation
  deadline.  Also folded per client into the ledger's tardiness
  columns.
- ``limit_stall_ns``: per stalled batch/level (committed nothing with
  work queued), time until the earliest queued head becomes eligible:
  ``max(min over queued heads of min(resv, limit) - now, 0)``.
- ``commit_size``: per batch/level, the committed decision count
  (bucket 0 = zero-commit batches).

Granularity: one observation per committed sort unit's entry head
(prefix: every decision; chain: the unit's entry serve -- induced
constraint serves are debt catch-up at the same boundary, not
separately-deadlined decisions), and for the calendar engine one per
client per LEVEL (bucketed ladder level == one minstop batch, so
bucketed-L telemetry equals the composition of L minstop batches
exactly -- the same equality the calendar digest gate pins for
decisions).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# -- histogram families ------------------------------------------------
HIST_DECISION_LATENCY = 0   # weight-phase entry: now - effective prop tag
HIST_RESV_TARDINESS = 1     # constraint-phase entry: now - resv tag
HIST_LIMIT_STALL = 2        # stalled batch: time to next eligibility
HIST_COMMIT_SIZE = 3        # per batch/level committed decisions
NUM_HISTS = 4

HIST_NAMES = ("decision_latency_ns", "resv_tardiness_ns",
              "limit_stall_ns", "commit_size")

NUM_BUCKETS = 48
HIST_SUM_COL = NUM_BUCKETS          # value-sum rides as column 48

# host powers-of-two table (int64); device code folds it in at trace
# time -- a module-level jnp array would leak a tracer when this module
# is imported lazily under a jit trace (the obs.device _HWM_MASK bug)
_POWERS = (np.int64(1) << np.arange(NUM_BUCKETS - 1)).astype(np.int64)

# Prometheus-facing upper bounds: bucket 0 -> le=0; bucket i -> the
# largest value it can hold (2^i - 1); bucket 47 is the clipped open
# bucket and drains as le=+Inf.
BUCKET_BOUNDS = tuple([0.0] + [float((1 << i) - 1)
                               for i in range(1, NUM_BUCKETS - 1)]
                      + [float("inf")])


def hist_zero() -> jnp.ndarray:
    return jnp.zeros((NUM_HISTS, NUM_BUCKETS + 1), dtype=jnp.int64)


def bucket_index(v: jnp.ndarray) -> jnp.ndarray:
    """Exact log2 bucket of int64 values (elementwise): 0 for v <= 0,
    else ``floor(log2(v)) + 1`` clipped to 47.  Computed as a dense
    count of passed power-of-two thresholds -- deterministic on every
    backend, no float rounding at bucket boundaries."""
    v = jnp.asarray(v, dtype=jnp.int64)
    powers = jnp.asarray(_POWERS)
    return jnp.sum(v[..., None] >= powers, axis=-1).astype(jnp.int32)


def hist_observe(h: jnp.ndarray, family: int, values, mask
                 ) -> jnp.ndarray:
    """Fold a dense masked batch of observations into one family:
    one-hot bucket compares + a sum reduction (the radix-histogram
    idiom -- scatters serialize on TPU).  Negative values clamp to
    bucket 0 and contribute 0 to the sum."""
    v = jnp.maximum(jnp.asarray(values, dtype=jnp.int64), 0)
    mask = jnp.asarray(mask, dtype=bool)
    idx = bucket_index(v)
    onehot = (idx[:, None]
              == jnp.arange(NUM_BUCKETS, dtype=jnp.int32)[None, :]) \
        & mask[:, None]
    counts = jnp.sum(onehot, axis=0).astype(jnp.int64)
    total = jnp.sum(jnp.where(mask, v, 0))
    row = jnp.concatenate([counts, total[None]])
    return h.at[family].add(row)


def hist_observe_scalar(h: jnp.ndarray, family: int, value, weight
                        ) -> jnp.ndarray:
    """One (possibly weight-0) scalar observation -- per-batch values
    like the commit size or a stall duration."""
    v = jnp.maximum(jnp.asarray(value, dtype=jnp.int64), 0)
    w = jnp.asarray(weight, dtype=jnp.int64)
    idx = bucket_index(v)
    row = jnp.where(jnp.arange(NUM_BUCKETS, dtype=jnp.int32) == idx,
                    w, jnp.int64(0))
    row = jnp.concatenate([row, (v * w)[None]])
    return h.at[family].add(row)


def hist_combine(a, b):
    """Merge two histogram blocks (pure counters: add).  Associative
    and commutative -- epochs/shards merge in any order."""
    return a + b


def hist_fold(h, delta, live):
    """Fold a batch delta gated on a scalar liveness flag (the tag32
    dead-batch gate: a tripped batch's telemetry must not land)."""
    return h + jnp.where(live, delta, jnp.zeros_like(delta))


def hist_mesh_reduce(h: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """In-graph mesh merge: every cell is a counter, so the collective
    is one ``psum`` -- the histogram analog of
    ``obs.device.metrics_mesh_reduce``."""
    from jax import lax

    return lax.psum(h, axis_name)


def hist_dict(h) -> dict:
    """Name a fetched histogram block (host side): per family the
    bucket counts, count, and sum."""
    a = np.asarray(h, dtype=np.int64)
    out = {}
    for i, name in enumerate(HIST_NAMES):
        counts = a[i, :NUM_BUCKETS]
        out[name] = {"buckets": counts.tolist(),
                     "count": int(counts.sum()),
                     "sum": int(a[i, HIST_SUM_COL])}
    return out


def hist_percentile(h, family: int, q: float) -> float:
    """Host-side percentile estimate from the log2 buckets: the UPPER
    bound of the bucket where the cumulative count crosses ``q`` --
    log2-quantized, so a reported p99 is within one octave of the true
    value (and never under-reports).  Returns 0.0 on an empty family."""
    a = np.asarray(h, dtype=np.int64)
    counts = a[family, :NUM_BUCKETS]
    total = int(counts.sum())
    if total == 0:
        return 0.0
    target = q * total
    cum = np.cumsum(counts)
    i = int(np.searchsorted(cum, target, side="left"))
    i = min(i, NUM_BUCKETS - 1)
    if i == 0:
        return 0.0
    # open top bucket reports its nominal next-octave bound
    return float((1 << (i + 1)) - 1) if i == NUM_BUCKETS - 1 \
        else float((1 << i) - 1)


def hist_mean(h, family: int) -> float:
    a = np.asarray(h, dtype=np.int64)
    n = int(a[family, :NUM_BUCKETS].sum())
    return float(a[family, HIST_SUM_COL]) / n if n else 0.0


def publish_hists(registry, h, prefix: str = "dmclock",
                  labels=None) -> None:
    """Expose a fetched histogram block as proper Prometheus histogram
    families (``_bucket``/``_sum``/``_count``) through the host
    registry: get-or-create a fixed-bucket histogram per family at the
    log2 bounds and overwrite its counts (the device block is itself
    cumulative per run, so set-not-add is the correct drain)."""
    a = np.asarray(h, dtype=np.int64)
    for i, name in enumerate(HIST_NAMES):
        m = registry.histogram(
            f"{prefix}_{name}",
            "on-device log2-bucketed QoS histogram "
            "(docs/OBSERVABILITY.md)",
            labels=labels, buckets=BUCKET_BOUNDS)
        m.set_counts(a[i, :NUM_BUCKETS].tolist(),
                     float(a[i, HIST_SUM_COL]))


# ----------------------------------------------------------------------
# per-client conformance ledger
# ----------------------------------------------------------------------

LED_OPS = 0         # decisions served
LED_RESV_OPS = 1    # constraint-phase decisions
LED_LIMIT_BREAKS = 2  # AtLimit::Allow limit-break entries
LED_TARD_SUM = 3    # reservation tardiness sum, ns (entry-head obs)
LED_TARD_MAX = 4    # reservation tardiness max, ns (merge: max)
LED_COLS = 5

LEDGER_COL_NAMES = ("ops", "resv_ops", "limit_breaks",
                    "tardiness_sum_ns", "tardiness_max_ns")

# max-merged columns, as a host constant (same lazy-import-under-trace
# rule as the histogram powers table)
_LED_MAX_MASK = np.zeros((LED_COLS,), dtype=bool)
_LED_MAX_MASK[LED_TARD_MAX] = True


def ledger_zero(n: int) -> jnp.ndarray:
    return jnp.zeros((n, LED_COLS), dtype=jnp.int64)


def ledger_combine(a, b):
    """Merge two ledgers over the SAME client set (counter columns
    add, the tardiness max maxes) -- associative and commutative, the
    metrics-vector algebra applied per client."""
    return jnp.where(_LED_MAX_MASK, jnp.maximum(a, b), a + b)


def ledger_fold(led, delta, live):
    """Fold a batch delta gated on liveness (all delta entries are
    >= 0, so a zeroed dead-batch delta is the merge identity)."""
    return ledger_combine(led,
                          jnp.where(live, delta, jnp.zeros_like(delta)))


def ledger_mesh_reduce(led: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """In-graph mesh merge for REPLICATED client sets (every shard
    holds rows for the same [N] clients, e.g. per-server ledgers in a
    cluster): counter columns ``psum``, the max column ``pmax``.
    Sharded-client layouts concatenate instead -- do not reduce
    disjoint client rows."""
    from jax import lax

    from .device import pmax_i64

    return jnp.where(_LED_MAX_MASK, pmax_i64(led, axis_name),
                     lax.psum(led, axis_name))


def ledger_combine_np(acc, *ledgers):
    """Host-side mirror of :func:`ledger_combine` (numpy); derives the
    max column from the same mask so the merges cannot diverge."""
    acc = np.asarray(acc, dtype=np.int64)
    for v in ledgers:
        v = np.asarray(v)
        acc = np.where(_LED_MAX_MASK, np.maximum(acc, v), acc + v)
    return acc


def ledger_totals(led) -> dict:
    """Column totals of a fetched ledger (host side): counters sum,
    tardiness max maxes -- the scalar view bench lines carry."""
    a = np.asarray(led, dtype=np.int64)
    out = {}
    for i, name in enumerate(LEDGER_COL_NAMES):
        out[name] = int(a[:, i].max()) if _LED_MAX_MASK[i] \
            else int(a[:, i].sum())
    return out


def ledger_rows(led, limit: int = None) -> list:
    """Per-client dict rows of a fetched ledger (host side), including
    the derived mean tardiness."""
    a = np.asarray(led, dtype=np.int64)
    n = a.shape[0] if limit is None else min(limit, a.shape[0])
    rows = []
    for c in range(n):
        r = {"client": c}
        r.update({name: int(a[c, i])
                  for i, name in enumerate(LEDGER_COL_NAMES)})
        r["tardiness_mean_ns"] = (a[c, LED_TARD_SUM]
                                  / max(int(a[c, LED_RESV_OPS]), 1))
        rows.append(r)
    return rows


def publish_ledger(registry, led, prefix: str = "dmclock_ledger",
                   labels=None) -> None:
    """Fold a fetched ledger's column totals into a host registry as
    gauges (per-client series would explode the scrape; the full table
    drains through the JSON paths instead)."""
    for name, value in ledger_totals(led).items():
        registry.gauge(f"{prefix}_{name}",
                       "device conformance-ledger column total "
                       "(docs/OBSERVABILITY.md)",
                       labels=labels).set(value)
