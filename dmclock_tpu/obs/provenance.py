"""Decision provenance plane: device-resident "why" records.

The planes shipped so far answer *what* a run did -- counts
(``obs.device``), tails (``obs.histograms``), wall time
(``obs.spans``), conformance (``obs.slo``), cost (``obs.capacity``) --
but when the SLO plane flags a client's window as violating, nothing
can say *why*: was the client limit-capped, out-competed on
proportional tags, or starved behind tardy reservations?  The mClock
algorithm's whole identity is the per-decision phase choice
(reservation -> ready -> weight -> limit-break, reference
do_next_request :1115-1186), and the decision stream used to discard
everything about that choice except the winner.  This module keeps the
choice's *context* in the data path (the RackSched per-decision
queue-state-visibility thesis, PAPERS.md), under the same contract as
every prior plane: pure reductions over arrays the engines already
materialize, riding the epoch-scan carries, decisions bit-identical
with the plane on or off (tests/test_provenance.py, ci.sh provenance
smoke).

**The provenance block** (:class:`ProvBlock`):

- ``margin_hist`` (``int64[NUM_BUCKETS + 1]``): log2 histogram (+
  ns-sum column, the ``obs.histograms`` bucket layout) of per-record
  **winner margins** -- the runner-up candidate's unified key minus the
  winner's, the "how close was this choice" signal.  For the sorted
  engines the runner-up at the instant decision *j* commits is exactly
  ``min(next sorted entry, min exit key of the already-served prefix)``
  -- both arrays the prefix condition already materializes -- so the
  margin is exact, not an estimate.  For the calendar engine the margin
  is the distance from a client's last unit-entry key to the committed
  boundary ``B_eff`` (how much headroom the boundary left it).
  Margins >= ~2^32 ns mean the runner-up sat in a LOWER phase (the
  packed key's class bits dominate): the phase ladder, not the tag,
  decided.  A record with no runner-up (sole candidate) observes
  nothing.
- ``scal`` (``int64[PS_FIELDS]``): per-batch aggregates -- the
  limit-gate state (how many clients sat queued but non-candidate
  behind their limit tag at batch entry), the eligible-set depth, the
  winning phase (the minimum class among candidates -- classes sort
  first in the unified key, so the batch's min class IS its first
  winner's phase), and the starvation high-watermark.
- ``last_served`` (``int64[N]``): per-client watermark of the virtual
  time of the last committed serve (a never-served client holds the
  block-creation baseline, so staleness is measured from when the
  block was armed).  Feeds the starvation detector: at every batch
  entry, ``now - last_served`` over backlogged clients, max'd into
  ``PS_STARVE_MAX``.

Merge algebra matches the metrics vector: counter rows add, ``*_MAX``
rows and ``last_served`` max (:func:`prov_combine` /
:func:`prov_mesh_reduce` psum/pmax).  The tag32 dead-batch rule is a
whole-block select (:func:`prov_select`): a tripped batch's
observations never land.

**Starvation detector** (:class:`StarvationMonitor`): host side, fed
at drain points.  Publishes the ``dmclock_starvation_*`` families and
fires a once-per-episode ``client_starved`` warning through the PR-7
watchdog's external-warning hook (or a log line) when a backlogged
client's time-since-service crosses the threshold; a client served
again re-arms its episode.

**Per-shard pressure gauges** (:func:`pressure_vec` /
:func:`publish_shard_pressure`): the placement signal the ROADMAP
rack-scheduling item needs -- live/peak eligible-set depth, backlog,
and a head-wait starvation watermark (``now - head_arrival`` over
queued heads: how long the current head has sat unserved, computable
from any shard's :class:`EngineState` alone) per shard, merged across
the mesh with the usual psum/pmax collective
(:func:`pressure_mesh_reduce`) and published as
``dmclock_shard_pressure_*``.

Offline, ``scripts/explain.py`` joins the flight ring (now carrying
margin/gate columns, ``obs.flight``), the decision trace (schema v2,
``obs.trace``), and the SLO window ring into a ranked causal
attribution per (client, window): limit_capped vs out_competed vs
reservation_tardy vs no_demand.
"""

from __future__ import annotations

import json
import sys
from typing import Callable, List, NamedTuple, Optional, Set

import numpy as np

from . import histograms as obshist

# -- scalar rows -------------------------------------------------------
PS_BATCHES = 0        # live batches observed
PS_GATED_BATCHES = 1  # batches with >= 1 limit-gated client
PS_GATE_SUM = 2       # sum over batches of limit-gated client count
PS_GATE_MAX = 3       # max limit-gated count in one batch  (merge: max)
PS_ELIG_SUM = 4       # sum over batches of eligible-set depth
PS_ELIG_MAX = 5       # max eligible-set depth               (merge: max)
PS_WIN_RESV = 6       # batches won by the constraint phase (min cls 0)
PS_WIN_PROP = 7       # batches won by the weight phase     (min cls 1)
PS_WIN_LB = 8         # batches won by a limit-break        (min cls 2)
PS_STARVE_MAX = 9     # max time-since-service over backlogged clients
#                       observed at any batch entry, ns     (merge: max)
PS_FIELDS = 10

PS_NAMES = ("batches", "gated_batches", "limit_gate_sum",
            "limit_gate_max", "eligible_depth_sum",
            "eligible_depth_max", "phase_wins_reservation",
            "phase_wins_weight", "phase_wins_limit_break",
            "starvation_max_ns")

# max-merged rows as a HOST constant (the obs.device _HWM_MASK rule:
# a module-level jnp array would leak a tracer under a lazy import
# inside a jit trace)
_PS_MAX_MASK = np.zeros((PS_FIELDS,), dtype=bool)
for _i in (PS_GATE_MAX, PS_ELIG_MAX, PS_STARVE_MAX):
    _PS_MAX_MASK[_i] = True


class ProvBlock(NamedTuple):
    """The device-resident provenance accumulator (see module doc)."""

    margin_hist: object   # int64[NUM_BUCKETS + 1]
    scal: object          # int64[PS_FIELDS]
    last_served: object   # int64[N]; a never-served client holds the
    #                       block-creation baseline (prov_init now_ns)


def prov_init(n: int, now_ns: int = 0) -> ProvBlock:
    """Fresh block.  ``now_ns`` is the measurement baseline the
    ``last_served`` watermark starts from: staleness of a
    never-served client is measured from BLOCK CREATION, not from
    virtual t=0 -- a block armed mid-run (after a warm-up) must not
    read every backlogged client as starved since the beginning of
    time."""
    import jax.numpy as jnp

    return ProvBlock(
        margin_hist=jnp.zeros((obshist.NUM_BUCKETS + 1,),
                              dtype=jnp.int64),
        scal=jnp.zeros((PS_FIELDS,), dtype=jnp.int64),
        last_served=jnp.full((n,), jnp.int64(now_ns)))


def _margin_row(margins):
    """One batch's margin-histogram delta from a masked margin array
    (``-1`` = no observation): one-hot bucket compares + a sum
    reduction, the ``obs.histograms.hist_observe`` fold on a single
    standalone row."""
    import jax.numpy as jnp

    m = jnp.asarray(margins, dtype=jnp.int64)
    mask = m >= 0
    v = jnp.maximum(m, 0)
    idx = obshist.bucket_index(v)
    onehot = (idx[:, None] == jnp.arange(obshist.NUM_BUCKETS,
                                         dtype=jnp.int32)[None, :]) \
        & mask[:, None]
    counts = jnp.sum(onehot, axis=0).astype(jnp.int64)
    total = jnp.sum(jnp.where(mask, v, 0))
    return jnp.concatenate([counts, total[None]])


def prov_observe(prov: ProvBlock, *, now, elig, gated, win_cls,
                 served_pc, margins=None) -> ProvBlock:
    """Fold one batch/level's observations (see module doc for the
    semantics of each row).  Pure reductions over the entry
    classification and commit arrays the batch already computed, so
    the decision stream cannot be perturbed.

    ``elig``/``gated`` are bool[N] masks over the batch-ENTRY state
    (candidates / queued-but-non-candidate clients); ``win_cls`` is
    the scalar min class among candidates (CLS_NONE = no candidate);
    ``served_pc`` int32[N] decisions committed per client;
    ``margins`` (optional) the per-record margin array, ``-1`` = no
    observation.  The caller gates liveness with
    :func:`prov_select` (the tag32 dead-batch rule)."""
    import jax.numpy as jnp

    now = jnp.asarray(now, dtype=jnp.int64)
    elig = jnp.asarray(elig, dtype=bool)
    gated = jnp.asarray(gated, dtype=bool)
    elig_n = jnp.sum(elig).astype(jnp.int64)
    gate_n = jnp.sum(gated).astype(jnp.int64)
    backlog = elig | gated
    # staleness read at batch ENTRY, before this batch's serves land
    starve = jnp.max(jnp.where(backlog, now - prov.last_served,
                               jnp.int64(0)))
    win_cls = jnp.asarray(win_cls, dtype=jnp.int32)
    wins = (win_cls == jnp.arange(3, dtype=jnp.int32)) \
        .astype(jnp.int64)
    delta = jnp.stack([
        jnp.int64(1), (gate_n > 0).astype(jnp.int64), gate_n,
        gate_n, elig_n, elig_n, wins[0], wins[1], wins[2], starve])
    scal = jnp.where(jnp.asarray(_PS_MAX_MASK),
                     jnp.maximum(prov.scal, delta), prov.scal + delta)
    hist = prov.margin_hist if margins is None \
        else prov.margin_hist + _margin_row(margins)
    served = jnp.asarray(served_pc) > 0
    last = jnp.where(served, now, prov.last_served)
    return ProvBlock(margin_hist=hist, scal=scal, last_served=last)


def prov_select(live, new: ProvBlock, old: ProvBlock) -> ProvBlock:
    """Whole-block liveness gate (the tag32 dead-batch rule): a dead
    batch's observations -- including its ``last_served`` writes --
    never land."""
    import jax
    import jax.numpy as jnp

    live = jnp.asarray(live, dtype=bool)
    return jax.tree.map(lambda a, b: jnp.where(live, a, b), new, old)


def prov_combine(a: ProvBlock, b: ProvBlock) -> ProvBlock:
    """Merge two blocks over the SAME client set: histogram + counter
    rows add, ``*_MAX`` rows and ``last_served`` max -- associative
    and commutative, the metrics-vector algebra."""
    import jax.numpy as jnp

    return ProvBlock(
        margin_hist=a.margin_hist + b.margin_hist,
        scal=jnp.where(jnp.asarray(_PS_MAX_MASK),
                       jnp.maximum(a.scal, b.scal), a.scal + b.scal),
        last_served=jnp.maximum(a.last_served, b.last_served))


def prov_mesh_reduce(p: ProvBlock, axis_name: str) -> ProvBlock:
    """In-graph mesh merge for REPLICATED client sets: counters psum,
    max rows + ``last_served`` pmax (the ledger collective applied per
    provenance field)."""
    import jax.numpy as jnp
    from jax import lax

    from .device import pmax_i64

    return ProvBlock(
        margin_hist=lax.psum(p.margin_hist, axis_name),
        scal=jnp.where(jnp.asarray(_PS_MAX_MASK),
                       pmax_i64(p.scal, axis_name),
                       lax.psum(p.scal, axis_name)),
        last_served=pmax_i64(p.last_served, axis_name))


def prov_from_arrays(margin_hist, scal, last_served) -> ProvBlock:
    """Rebuild a ProvBlock from checkpointed numpy leaves (the
    ``robust.supervisor`` payload round-trip)."""
    import jax.numpy as jnp

    return ProvBlock(
        margin_hist=jnp.asarray(margin_hist, dtype=jnp.int64),
        scal=jnp.asarray(scal, dtype=jnp.int64),
        last_served=jnp.asarray(last_served, dtype=jnp.int64))


# ----------------------------------------------------------------------
# host side: percentiles, dict views, publishing
# ----------------------------------------------------------------------

def margin_percentile(prov, q: float) -> float:
    """Margin percentile from the log2 buckets (bucket-upper-bound, so
    never under-reported -- the ``obs.histograms`` quantization math on
    the standalone margin row)."""
    h = np.asarray(getattr(prov, "margin_hist", prov), dtype=np.int64)
    block = np.zeros((obshist.NUM_HISTS, obshist.NUM_BUCKETS + 1),
                     dtype=np.int64)
    block[0] = h
    return obshist.hist_percentile(block, 0, q)


def prov_dict(prov) -> dict:
    """Name a fetched block (host side): the scalar rows plus the
    derived margin percentiles and the limit-gate share."""
    import jax

    scal = np.asarray(jax.device_get(prov.scal), dtype=np.int64)
    out = {name: int(scal[i]) for i, name in enumerate(PS_NAMES)}
    batches = max(out["batches"], 1)
    out["limit_gate_share"] = out["gated_batches"] / batches
    out["eligible_depth_mean"] = out["eligible_depth_sum"] / batches
    out["margin_p50_ns"] = margin_percentile(prov, 0.50)
    out["margin_p99_ns"] = margin_percentile(prov, 0.99)
    h = np.asarray(jax.device_get(prov.margin_hist), dtype=np.int64)
    n = int(h[:obshist.NUM_BUCKETS].sum())
    out["margin_count"] = n
    out["margin_mean_ns"] = float(h[obshist.HIST_SUM_COL]) / n \
        if n else 0.0
    return out


def stale_clients(prov, now_ns: int, threshold_ns: int,
                  backlog=None) -> List[dict]:
    """Clients whose time-since-service exceeds ``threshold_ns`` at
    ``now_ns`` (host side), worst first.  ``backlog`` (optional
    int[N]) restricts to clients with queued work -- without it, a
    never-served idle client would read as infinitely starved."""
    import jax

    last = np.asarray(jax.device_get(prov.last_served),
                      dtype=np.int64)
    stale = np.int64(now_ns) - last
    mask = stale > threshold_ns
    if backlog is not None:
        mask &= np.asarray(jax.device_get(backlog)) > 0
    idx = np.nonzero(mask)[0]
    rows = [{"client": int(c), "stale_ns": int(stale[c]),
             "last_served_ns": int(last[c])} for c in idx]
    rows.sort(key=lambda r: -r["stale_ns"])
    return rows


def publish_provenance(registry, prov, labels=None) -> None:
    """Fold a fetched block into a host registry:
    ``dmclock_provenance_*`` gauges (margin percentiles, gate share,
    eligible depth) and the ``dmclock_starvation_max_ns`` watermark."""
    d = prov_dict(prov)
    for key in ("margin_p50_ns", "margin_p99_ns", "limit_gate_share",
                "eligible_depth_mean", "eligible_depth_max",
                "phase_wins_reservation", "phase_wins_weight",
                "phase_wins_limit_break"):
        registry.gauge(f"dmclock_provenance_{key}",
                       "decision provenance plane scalar "
                       "(docs/OBSERVABILITY.md)",
                       labels=labels).set(float(d[key]))
    registry.gauge("dmclock_starvation_max_ns",
                   "max time-since-service over backlogged clients "
                   "observed at any batch entry (provenance plane)",
                   labels=labels).set(float(d["starvation_max_ns"]))


# ----------------------------------------------------------------------
# starvation detector (host half)
# ----------------------------------------------------------------------

def _stderr_log(line: str) -> None:
    print(line, file=sys.stderr)


class StarvationMonitor:
    """Once-per-episode ``client_starved`` warnings over the
    provenance watermark.

    Fed at drain points with the fetched ``last_served`` watermark (or
    a whole ProvBlock), the current virtual time, and the per-client
    backlog; fires on the rising edge of ``now - last_served >
    threshold_ns`` per client and re-arms when the client is served
    again (staleness back under threshold).  Warnings route through a
    PR-7 :class:`~.watchdog.Watchdog`'s ``external_warning`` hook when
    attached (one warning stream + counter for the run), else a
    ``# starvation:`` JSON log line.  Deterministic: the same
    watermark stream fires the same episodes, so a resumed run (the
    watermark rides the rotation checkpoints) reconstructs them."""

    def __init__(self, threshold_ns: int, *, watchdog=None,
                 registry=None,
                 log: Callable[[str], None] = _stderr_log):
        self.threshold_ns = int(threshold_ns)
        self._watchdog = watchdog
        self._log = log
        self.active: Set[int] = set()
        self.fired: List[dict] = []
        self.episodes_total = 0
        self._counter = None
        self._max_gauge = None
        self._stale_gauge = None
        if registry is not None:
            self.attach_registry(registry)

    def attach_registry(self, registry) -> None:
        self._counter = registry.counter(
            "dmclock_starvation_episodes_total",
            "client_starved episodes fired (once per episode; "
            "provenance plane, docs/OBSERVABILITY.md)")
        self._max_gauge = registry.gauge(
            "dmclock_starvation_max_ns",
            "max time-since-service over backlogged clients "
            "(provenance plane)")
        self._stale_gauge = registry.gauge(
            "dmclock_starvation_stale_clients",
            "backlogged clients currently past the starvation "
            "threshold (provenance plane)")

    def observe(self, prov, now_ns: int, backlog=None) -> List[dict]:
        """One drain-point pass; returns the warnings fired (rising
        edges only)."""
        rows = stale_clients(prov, now_ns, self.threshold_ns,
                             backlog=backlog)
        over = {r["client"] for r in rows}
        # clients back under the threshold re-arm their episodes
        self.active &= over
        out = []
        for r in rows:
            if r["client"] in self.active:
                continue
            self.active.add(r["client"])
            w = {"kind": "client_starved", **r,
                 "threshold_ns": self.threshold_ns}
            out.append(w)
            self.fired.append(w)
            self.episodes_total += 1
            if self._counter is not None:
                self._counter.inc()
            if self._watchdog is not None:
                self._watchdog.external_warning(w)
            else:
                self._log("# starvation: "
                          + json.dumps(w, separators=(",", ":")))
        if self._max_gauge is not None:
            worst = rows[0]["stale_ns"] if rows else 0
            self._max_gauge.set(float(worst))
            self._stale_gauge.set(float(len(over)))
        return out


# ----------------------------------------------------------------------
# per-shard pressure gauges (the rack-scheduling placement signal)
# ----------------------------------------------------------------------

PRESS_ELIG = 0       # live eligible-set depth            (merge: add)
PRESS_BACKLOG = 1    # queued requests across clients     (merge: add)
PRESS_ELIG_PEAK = 2  # peak eligible depth                (merge: max)
PRESS_WAIT_WM = 3    # head-wait starvation watermark, ns (merge: max)
PRESS_FIELDS = 4

PRESS_NAMES = ("eligible_live", "backlog", "eligible_peak",
               "head_wait_max_ns")

_PRESS_MAX_MASK = np.zeros((PRESS_FIELDS,), dtype=bool)
for _i in (PRESS_ELIG_PEAK, PRESS_WAIT_WM):
    _PRESS_MAX_MASK[_i] = True


def pressure_vec(engine_state, now):
    """One server's pressure vector (``int64[PRESS_FIELDS]``) from its
    own :class:`EngineState` -- computable on ANY shard with no extra
    state: live eligible-set depth (candidates at ``now``), backlog,
    the same value as peak (the mesh/time merges max it), and the
    head-wait watermark ``max(now - head_arrival)`` over queued heads
    (how long the current head has sat unserved -- the shard-local
    starvation signal)."""
    import jax.numpy as jnp

    from ..engine import fastpath

    now = jnp.asarray(now, dtype=jnp.int64)
    cls, _key = fastpath._classify(engine_state, now, True)
    elig = jnp.sum(cls != fastpath.CLS_NONE).astype(jnp.int64)
    has_req = engine_state.active & (engine_state.depth > 0)
    backlog = jnp.sum(jnp.where(has_req, engine_state.depth, 0)) \
        .astype(jnp.int64)
    wait = jnp.max(jnp.where(
        has_req,
        jnp.maximum(now - engine_state.head_arrival, 0),
        jnp.int64(0)))
    return jnp.stack([elig, backlog, elig, wait])


def pressure_combine_axis(mat):
    """Reduce stacked [S, PRESS_FIELDS] vectors along the leading axis
    (counters add, peaks max) -- the local-shard half of a mesh
    merge."""
    import jax.numpy as jnp

    return jnp.where(jnp.asarray(_PRESS_MAX_MASK),
                     jnp.max(mat, axis=0), jnp.sum(mat, axis=0))


def pressure_mesh_reduce(vec, axis_name: str):
    """In-graph mesh merge: counters psum, peaks pmax -- the
    ``metrics_mesh_reduce`` collective applied to the pressure
    fields."""
    import jax.numpy as jnp
    from jax import lax

    from .device import pmax_i64

    return jnp.where(jnp.asarray(_PRESS_MAX_MASK),
                     pmax_i64(vec, axis_name),
                     lax.psum(vec, axis_name))


def pressure_dict(vec) -> dict:
    v = np.asarray(vec, dtype=np.int64).reshape(-1)
    return {name: int(v[i]) for i, name in enumerate(PRESS_NAMES)}


def publish_shard_pressure(registry, per_shard, merged=None) -> None:
    """Publish a fetched [S, PRESS_FIELDS] per-shard matrix (plus the
    optional mesh-merged total) as ``dmclock_shard_pressure_*`` gauges
    labelled by shard -- the live placement signal power-of-two-choices
    routing reads."""
    mat = np.asarray(per_shard, dtype=np.int64)
    if mat.ndim == 1:
        mat = mat[None]
    for s in range(mat.shape[0]):
        for i, name in enumerate(PRESS_NAMES):
            registry.gauge(
                f"dmclock_shard_pressure_{name}",
                "per-shard scheduling pressure (provenance plane; "
                "docs/OBSERVABILITY.md)",
                labels={"shard": str(s)}).set(float(mat[s, i]))
    if merged is not None:
        for i, name in enumerate(PRESS_NAMES):
            registry.gauge(
                f"dmclock_shard_pressure_{name}",
                "mesh-merged scheduling pressure (provenance plane)",
                labels={"shard": "all"}) \
                .set(float(np.asarray(merged).reshape(-1)[i]))
