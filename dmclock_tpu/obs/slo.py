"""The SLO plane: device-resident windowed conformance.

The dmClock contract (reservation floor / limit ceiling / proportional
weight, mClock paper section 3) was only verifiable post-hoc: the PR-6
``int64[N, 5]`` ledger and the sim conformance tables are *cumulative*
artifacts, which smear across contract versions now that the lifecycle
plane (PR-9) replaces QoS triples mid-run.  RackSched's thesis
(PAPERS.md) is that microsecond-scale schedulers need continuously
evaluated, *windowed* policy-compliance signals -- this module supplies
them in three layers:

1. **Device window block** (``int64[N, W_FIELDS]``): per-client
   delivered ops, delivered cost, reservation-phase ops, tardy ops,
   limit-break ops, reservation-tardiness sum, and the window's
   contract-epoch id.  The counter columns accumulate inside all three
   epoch scans exactly like the PR-6 histograms/ledger (riding the scan
   carries, folded per batch gated on tag32 liveness, ``psum``-able
   with a ``pmax`` contract-epoch column); the decision stream is
   bit-identical with the block on or off (tests/test_slo.py).

2. **Window rolls pinned to the epoch grid**: a window is the epochs
   between two PR-5 checkpoint boundaries (= the PR-8 stream-chunk
   grid), so the round loop and the stream loop roll IDENTICALLY and a
   rotation checkpoint never splits a window -- crash equivalence
   extends to the block, the closed-window ring, and the contract-epoch
   counters with no new machinery (``robust.supervisor``).

3. **Host plane** (:class:`SloPlane`): a per-client **contract-epoch
   counter** bumped by every lifecycle REGISTER/UPDATE/EVICT, a bounded
   ring of closed windows per client -- each attributed to exactly one
   ``(client, contract_version)`` pair, read from the block's
   device-stamped contract-epoch column -- and per-window delivered-vs-
   contract conformance (share error against the weight entitlement,
   reservation-floor deficit, limit excess).  ``obs.alerts`` evaluates
   burn-rate rules over the ring; ``scripts/slo_report.py`` renders the
   exported JSONL offline.
"""

from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

# -- window block columns ----------------------------------------------
W_OPS = 0          # decisions delivered in the window
W_COST = 1         # delivered cost (sum of served request costs)
W_RESV_OPS = 2     # constraint-phase (reservation-eligible) decisions
W_TARDY_OPS = 3    # reservation entries served PAST their deadline
W_LB_OPS = 4       # AtLimit::Allow limit-break entries
W_TARD_SUM = 5     # reservation tardiness sum, ns (entry-head obs)
W_CEPOCH = 6       # contract-epoch id (host-stamped at window open)
W_FIELDS = 7

WINDOW_COL_NAMES = ("ops", "cost", "resv_ops", "tardy_ops", "lb_ops",
                    "tardiness_sum_ns", "contract_epoch")

# the contract-epoch column is metadata, not a counter: deltas carry 0
# there and merges keep the max (same host-constant-mask rule as the
# ledger's tardiness-max column -- a module-level jnp array would leak
# a tracer under a lazy import inside a jit trace)
_W_MAX_MASK = np.zeros((W_FIELDS,), dtype=bool)
_W_MAX_MASK[W_CEPOCH] = True


def window_zero(n: int):
    import jax.numpy as jnp

    return jnp.zeros((n, W_FIELDS), dtype=jnp.int64)


def window_delta(served_pc, cost_pc, resv_pc, tardy_pc, lb_pc,
                 tard_pc):
    """One batch/level's window contribution (``int64[N, W_FIELDS]``):
    pure stacking of per-client reductions the telemetry fold already
    computed, so the decision stream cannot be perturbed.  The
    contract-epoch column rides as zeros (max-merged, so the stamped
    accumulator value survives every fold)."""
    import jax.numpy as jnp

    cols = [jnp.asarray(c, dtype=jnp.int64)
            for c in (served_pc, cost_pc, resv_pc, tardy_pc, lb_pc,
                      tard_pc)]
    cols.append(jnp.zeros_like(cols[0]))
    return jnp.stack(cols, axis=1)


def window_combine(a, b):
    """Merge two window blocks over the SAME client set: counter
    columns add, the contract-epoch column maxes -- associative and
    commutative, the ledger algebra applied per window field."""
    import jax.numpy as jnp

    return jnp.where(_W_MAX_MASK, jnp.maximum(a, b), a + b)


def window_fold(w, delta, live):
    """Fold a batch delta gated on liveness (the tag32 dead-batch rule:
    a tripped batch's window contribution must not land)."""
    import jax.numpy as jnp

    return window_combine(w, jnp.where(live, delta,
                                       jnp.zeros_like(delta)))


def window_mesh_reduce(w, axis_name: str):
    """In-graph mesh merge for REPLICATED client sets: counter columns
    ``psum``, the contract-epoch column ``pmax`` (every shard stamps
    the same epochs) -- the window analog of
    ``obs.histograms.ledger_mesh_reduce``."""
    import jax.numpy as jnp
    from jax import lax

    from .device import pmax_i64

    return jnp.where(_W_MAX_MASK, pmax_i64(w, axis_name),
                     lax.psum(w, axis_name))


def window_combine_axis(mat):
    """Reduce a stacked ``[S, N, W_FIELDS]`` block along its leading
    shard axis (counter columns sum, contract-epoch max) -- the
    local half of a mesh merge (vmapped servers within a shard reduce
    here, then :func:`window_mesh_reduce` crosses the mesh), the
    window analog of ``obs.device.metrics_combine_axis``."""
    import jax.numpy as jnp

    return jnp.where(_W_MAX_MASK, jnp.max(mat, axis=0),
                     jnp.sum(mat, axis=0))


def window_combine_np(acc, *blocks):
    """Host-side mirror of :func:`window_combine` over numpy blocks
    (counters add, contract-epoch max) -- what the mesh merge tests
    compare the in-graph ``window_mesh_reduce`` result against, and
    what the supervisor uses to merge fetched per-shard blocks when
    no mesh program is live.  Derives the max column from the same
    ``_W_MAX_MASK`` as the device merge, so the two cannot drift."""
    acc = np.asarray(acc, dtype=np.int64)
    for b in blocks:
        b = np.asarray(b, dtype=np.int64)
        acc = np.where(_W_MAX_MASK, np.maximum(acc, b), acc + b)
    return acc


def publish_shard_windows(registry, blocks, merged=None,
                          workload: Optional[str] = None) -> None:
    """Publish per-shard window-block totals as ``dmclock_slo_window_*``
    gauges labelled by ``shard`` (the ROADMAP PR-10 fold-in: the
    cluster-wide delivered-vs-contract table keeps its per-shard
    decomposition visible), plus the mesh-merged cluster total under
    ``shard="all"``.  ``blocks`` is ``[S, N, W_FIELDS]`` (stacked) or
    an iterable of per-shard blocks; ``merged`` defaults to the host
    combine of the shards."""
    blocks = [np.asarray(b, dtype=np.int64) for b in blocks]
    if merged is None and blocks:
        merged = window_combine_np(np.zeros_like(blocks[0]), *blocks)

    def emit(block, shard: str) -> None:
        labels = {"shard": shard}
        if workload is not None:
            labels["workload"] = workload
        for name, val in window_totals(block).items():
            registry.gauge(
                f"dmclock_slo_window_{name}",
                "cluster-wide windowed conformance column, per shard "
                "(docs/OBSERVABILITY.md SLO plane; shard=all is the "
                "window_mesh_reduce merge)",
                labels=labels).set(float(val))

    for s, block in enumerate(blocks):
        emit(block, str(s))
    if merged is not None:
        emit(np.asarray(merged, dtype=np.int64), "all")


def stamp_cepoch(block, cepochs):
    """Write the per-slot contract-epoch ids into the block's
    :data:`W_CEPOCH` column (one cheap device launch per boundary --
    lifecycle ops apply only there, so the column is always current
    for the window that follows)."""
    import jax.numpy as jnp

    return block.at[:, W_CEPOCH].set(
        jnp.asarray(cepochs, dtype=jnp.int64))


def window_totals(block) -> dict:
    """Counter-column totals of a fetched block (host side) -- what
    the windowed-vs-cumulative cross-check sums against the ledger."""
    a = np.asarray(block, dtype=np.int64)
    return {name: int(a[:, i].sum())
            for i, name in enumerate(WINDOW_COL_NAMES)
            if i != W_CEPOCH}


# ----------------------------------------------------------------------
# host plane: contract epochs + closed-window ring + conformance
# ----------------------------------------------------------------------

RING_COLS = 12  # seq, cid, cepoch, e0, e1, ops, cost, resv_ops,
#                 tardy_ops, lb_ops, tard_sum_ns, backlog


@dataclasses.dataclass(frozen=True)
class ClosedWindow:
    """One client's closed window, attributed to exactly one
    ``(client, contract_epoch)`` pair.  ``backlog`` is the client's
    queue depth at close -- what separates a reservation-starved
    client (backlogged, undelivered) from an idle one."""

    seq: int          # global roll sequence number
    cid: int          # client id
    cepoch: int       # contract-epoch id (device-stamped)
    e0: int           # first epoch of the window
    e1: int           # one past the last epoch
    ops: int
    cost: int
    resv_ops: int
    tardy_ops: int
    lb_ops: int
    tard_sum_ns: int
    backlog: int

    def row(self) -> list:
        return [self.seq, self.cid, self.cepoch, self.e0, self.e1,
                self.ops, self.cost, self.resv_ops, self.tardy_ops,
                self.lb_ops, self.tard_sum_ns, self.backlog]

    @classmethod
    def from_row(cls, r) -> "ClosedWindow":
        r = [int(x) for x in r]
        return cls(*r)

    def to_json(self) -> dict:
        return {"seq": self.seq, "client": self.cid,
                "contract_epoch": self.cepoch,
                "e0": self.e0, "e1": self.e1, "ops": self.ops,
                "cost": self.cost, "resv_ops": self.resv_ops,
                "tardy_ops": self.tardy_ops, "lb_ops": self.lb_ops,
                "tardiness_sum_ns": self.tard_sum_ns,
                "backlog": self.backlog}


class SloPlane:
    """Host half of the windowed conformance plane for one run.

    Owns the per-client contract-epoch counters (bumped by lifecycle
    REGISTER/UPDATE/EVICT; a re-registered client continues its own
    monotone counter, so versions never repeat), the current + per-
    epoch contract log (reservation, weight, limit as RATES -- what
    delivered-vs-contract is priced against), and a bounded per-client
    ring of closed windows.  All state is plain data and encodes into
    flat ``slo_*`` checkpoint leaves, so a SIGKILLed run resumes with
    the identical attribution state (the crash-equivalence contract).

    Thread contract: single-owner (the epoch loop); the admin API
    reads through :meth:`summary` / :meth:`client_view`, which copy
    under the GIL over plain containers.
    """

    def __init__(self, capacity: int, *, dt_epoch_ns: int,
                 ring_depth: int = 64):
        self.capacity = int(capacity)
        self.dt_epoch_ns = int(dt_epoch_ns)
        self.ring_depth = max(int(ring_depth), 1)
        self.cepoch: Dict[int, int] = {}
        self.contracts: Dict[int, Tuple[float, float, float]] = {}
        # (cid, cepoch) -> (r, w, l): attribution for closed windows
        self.contract_log: Dict[Tuple[int, int],
                                Tuple[float, float, float]] = {}
        self.rings: Dict[int, deque] = {}
        self.window_seq = 0
        self.windows_closed = 0

    # -- contract-epoch bumps (the lifecycle plane calls these) --------
    def register(self, cid: int, r: float, w: float, l: float) -> int:
        """REGISTER bumps the client's contract epoch (a recycled id's
        counter continues from its last value -- a fresh tenancy is a
        fresh contract version) and records the contract."""
        cid = int(cid)
        ce = self.cepoch.get(cid, 0) + 1
        self.cepoch[cid] = ce
        self.contracts[cid] = (float(r), float(w), float(l))
        self.contract_log[(cid, ce)] = self.contracts[cid]
        return ce

    def update(self, cid: int, r: float, w: float, l: float) -> int:
        """Live ClientInfo UPDATE: same bump -- every closed window
        reports against exactly one contract version, never a blend."""
        return self.register(cid, r, w, l)

    def evict(self, cid: int) -> None:
        """EVICT ends the tenancy: the contract goes away, the epoch
        counter stays (monotone across re-registration), the ring
        keeps the departed client's closed windows."""
        self.contracts.pop(int(cid), None)

    def contract_of(self, cid: int, cepoch: int
                    ) -> Optional[Tuple[float, float, float]]:
        return self.contract_log.get((int(cid), int(cepoch)))

    # -- device-column stamping ----------------------------------------
    def cepoch_vector(self, cid_of_slot=None) -> np.ndarray:
        """Per-slot contract-epoch ids (0 for free slots) under the
        current slot layout; ``cid_of_slot=None`` = identity (closed-
        population runs, slot == client id)."""
        if cid_of_slot is None:
            return np.asarray([self.cepoch.get(c, 0)
                               for c in range(self.capacity)],
                              dtype=np.int64)
        cid_of_slot = np.asarray(cid_of_slot)
        return np.asarray(
            [self.cepoch.get(int(c), 0) if c >= 0 else 0
             for c in cid_of_slot], dtype=np.int64)

    def stamp(self, block, cid_of_slot=None):
        """Stamp the block's contract-epoch column from the host
        counters (capacity tracks the block: growth pads the vector)."""
        self.capacity = int(block.shape[0])
        return stamp_cepoch(block, self.cepoch_vector(cid_of_slot))

    # -- the roll ------------------------------------------------------
    def roll(self, block, e0: int, e1: int, *, cid_of_slot=None,
             depth=None, skip_idle: bool = False
             ) -> Tuple[object, List[ClosedWindow]]:
        """Close the window ``[e0, e1)``: fetch the block, append one
        :class:`ClosedWindow` per client with any activity (or a live
        contract -- a backlogged-but-starved client's empty window is
        the signal the reservation rule exists for), and return a
        fresh zeroed block with the contract-epoch column re-stamped.
        ``depth`` (optional ``int[N]``) records per-client backlog at
        close.  ``skip_idle`` drops zero-activity windows even for
        live contracts (large-N bench runs where every client serves
        anyway; keep it OFF when reservation-starvation must be
        detectable -- a starved client's window IS all zeros).
        Deterministic: same block + same counters -> same rows, so a
        resumed run re-rolls identically."""
        import jax

        a = np.asarray(jax.device_get(block), dtype=np.int64)
        self.capacity = a.shape[0]
        d = None if depth is None \
            else np.asarray(jax.device_get(depth), dtype=np.int64)
        closed: List[ClosedWindow] = []
        seq = self.window_seq
        for slot in range(a.shape[0]):
            if cid_of_slot is None:
                cid = slot
            else:
                cid = int(cid_of_slot[slot])
                if cid < 0:
                    continue
            row = a[slot]
            active = bool(row[:W_CEPOCH].any())
            if not active and (skip_idle
                               or cid not in self.contracts):
                continue
            if not active and row[W_CEPOCH] == 0:
                continue     # never registered on device yet
            w = ClosedWindow(
                seq=seq, cid=cid, cepoch=int(row[W_CEPOCH]),
                e0=int(e0), e1=int(e1),
                ops=int(row[W_OPS]), cost=int(row[W_COST]),
                resv_ops=int(row[W_RESV_OPS]),
                tardy_ops=int(row[W_TARDY_OPS]),
                lb_ops=int(row[W_LB_OPS]),
                tard_sum_ns=int(row[W_TARD_SUM]),
                backlog=0 if d is None else int(d[slot]))
            closed.append(w)
            self.rings.setdefault(cid, deque(maxlen=self.ring_depth)) \
                .append(w)
        self.window_seq += 1
        self.windows_closed += len(closed)
        fresh = self.stamp(window_zero(a.shape[0]), cid_of_slot)
        return fresh, closed

    # -- conformance ---------------------------------------------------
    def conformance_rows(self, closed: List[ClosedWindow]
                         ) -> List[dict]:
        """Delivered-vs-contract judgment of one roll's closed windows
        (all share ``[e0, e1)``): per client the delivered rate vs the
        reservation floor, the delivered cost share vs the weight
        entitlement among clients with demand, and the limit excess --
        each against the window's OWN contract version (no smearing
        across a mid-run update)."""
        if not closed:
            return []
        win_s = max((closed[0].e1 - closed[0].e0)
                    * self.dt_epoch_ns / 1e9, 1e-12)
        demand = [w for w in closed if w.ops > 0 or w.backlog > 0]
        total_cost = sum(w.cost for w in demand)
        wsum = 0.0
        for w in demand:
            c = self.contract_of(w.cid, w.cepoch)
            wsum += c[1] if c else 0.0
        rows = []
        for w in closed:
            c = self.contract_of(w.cid, w.cepoch) or (0.0, 0.0, 0.0)
            r, wt, lim = c
            rate = w.ops / win_s
            share = w.cost / total_cost if total_cost else 0.0
            entitled = (wt / wsum) if (wsum > 0 and
                                       (w.ops > 0 or w.backlog > 0)) \
                else 0.0
            share_err = (share - entitled) / max(entitled, 1e-9) \
                if entitled > 0 else 0.0
            resv_deficit = max(r - rate, 0.0) if r > 0 else 0.0
            # a reservation miss needs BACKLOG or tardiness: an idle
            # client under its floor is not a starved one
            resv_miss = bool(r > 0 and resv_deficit > 0.05 * r
                             and (w.backlog > 0 or w.tardy_ops > 0))
            limit_excess = max(rate - lim, 0.0) if lim > 0 else 0.0
            rows.append({
                **w.to_json(),
                "window_s": win_s, "rate": rate,
                "reservation": r, "weight": wt, "limit": lim,
                "share": share, "entitled_share": entitled,
                "share_err": share_err,
                "resv_deficit": resv_deficit, "resv_miss": resv_miss,
                "limit_excess": limit_excess,
                "tardiness_mean_ns": w.tard_sum_ns
                / max(w.resv_ops, 1),
            })
        return rows

    # -- views / reports -----------------------------------------------
    def ring_rows(self, cid: Optional[int] = None
                  ) -> List[ClosedWindow]:
        """Closed windows, oldest first (one client's ring or all,
        interleaved in close order).  Snapshots the containers before
        iterating: the admin HTTP thread reads this while the epoch
        loop's roll() inserts new clients, and iterating the live
        dict would intermittently raise mid-scrape."""
        if cid is not None:
            return list(self.rings.get(int(cid), ()))
        out = [w for ring in list(self.rings.values())
               for w in list(ring)]
        out.sort(key=lambda w: (w.seq, w.cid))
        return out

    def client_view(self, cid: int) -> dict:
        """One client's conformance view (the admin API's
        ``GET /clients/{id}/conformance``).  Each roll group is
        judged ONCE (the client appears in a given seq at most once)
        -- re-judging the full group per ring window would make one
        GET O(ring_depth x live_clients) on the HTTP thread.

        Judgments reflect the SURVIVING ring: once a busier peer's
        window for the same roll has been evicted from its own ring,
        the share denominators here are computed over the remaining
        set and can differ from the at-close judgment (the slo_log
        JSONL is the at-close record; this view is a live ring
        inspection, not an archive)."""
        cid = int(cid)
        want = {w.seq for w in list(self.rings.get(cid, ()))}
        grouped: Dict[int, List[ClosedWindow]] = {}
        for w in self.ring_rows():
            if w.seq in want:
                grouped.setdefault(w.seq, []).append(w)
        rows = []
        for seq in sorted(grouped):
            judged = self.conformance_rows(grouped[seq])
            rows += [r for r in judged if r["client"] == cid]
        return {"id": cid,
                "contract_epoch": self.cepoch.get(cid, 0),
                "contract": self.contracts.get(cid),
                "windows": rows}

    def summary(self) -> dict:
        return {"windows_closed": int(self.windows_closed),
                "rolls": int(self.window_seq),
                "clients_tracked": len(self.rings),
                "live_contracts": len(self.contracts),
                "ring_depth": self.ring_depth}

    def export_jsonl(self, path: str, closed: List[ClosedWindow],
                     judged: bool = True) -> int:
        """Append one roll's closed windows (judged rows when
        ``judged``) as JSONL -- the ``scripts/slo_report.py`` feed.
        Fail-soft is the CALLER's job (telemetry must never kill the
        run, but which exceptions are survivable is loop-specific)."""
        rows = self.conformance_rows(closed) if judged \
            else [w.to_json() for w in closed]
        with open(path, "a") as fh:
            for r in rows:
                fh.write(json.dumps(r) + "\n")
        return len(rows)

    # -- checkpoint round-trip -----------------------------------------
    def encode(self) -> dict:
        """Flat ``slo_*`` leaves for the PR-5 rotation payload."""
        ce = np.asarray(sorted((c, e) for c, e in self.cepoch.items()),
                        dtype=np.int64).reshape(len(self.cepoch), 2)
        con = np.asarray(
            [[c, e, r, w, l]
             for (c, e), (r, w, l) in sorted(self.contract_log.items())],
            dtype=np.float64).reshape(len(self.contract_log), 5)
        live = np.asarray(sorted(self.contracts), dtype=np.int64)
        ring = np.asarray([w.row() for w in self.ring_rows()],
                          dtype=np.int64).reshape(-1, RING_COLS)
        return {"slo_cepoch": ce, "slo_contracts": con,
                "slo_live": live, "slo_ring": ring,
                "slo_scalars": np.asarray(
                    [self.window_seq, self.windows_closed,
                     self.ring_depth], dtype=np.int64)}

    @classmethod
    def load(cls, payload: dict, *, capacity: int,
             dt_epoch_ns: int,
             ring_depth: Optional[int] = None) -> "SloPlane":
        """``ring_depth`` overrides the checkpointed depth BEFORE the
        rings are rebuilt, so every client's deque gets the new
        maxlen (an override applied after load would leave restored
        clients at the old depth and new registrants at the new
        one)."""
        sc = np.asarray(payload["slo_scalars"], dtype=np.int64)
        p = cls(capacity, dt_epoch_ns=dt_epoch_ns,
                ring_depth=int(sc[2]) if ring_depth is None
                else ring_depth)
        p.window_seq = int(sc[0])
        p.windows_closed = int(sc[1])
        for c, e in np.asarray(payload["slo_cepoch"],
                               dtype=np.int64).reshape(-1, 2):
            p.cepoch[int(c)] = int(e)
        for row in np.asarray(payload["slo_contracts"],
                              dtype=np.float64).reshape(-1, 5):
            p.contract_log[(int(row[0]), int(row[1]))] = \
                (float(row[2]), float(row[3]), float(row[4]))
        for c in np.asarray(payload["slo_live"],
                            dtype=np.int64).reshape(-1):
            ce = p.cepoch.get(int(c), 0)
            con = p.contract_log.get((int(c), ce))
            if con is not None:
                p.contracts[int(c)] = con
        for row in np.asarray(payload["slo_ring"],
                              dtype=np.int64).reshape(-1, RING_COLS):
            w = ClosedWindow.from_row(row)
            p.rings.setdefault(w.cid, deque(maxlen=p.ring_depth)) \
                .append(w)
        return p

    @staticmethod
    def empty_leaves() -> dict:
        """Zero-size ``slo_*`` leaves for jobs with the plane off (the
        structure-from-config checkpoint convention)."""
        return {"slo_cepoch": np.zeros((0, 2), dtype=np.int64),
                "slo_contracts": np.zeros((0, 5), dtype=np.float64),
                "slo_live": np.zeros((0,), dtype=np.int64),
                "slo_ring": np.zeros((0, RING_COLS), dtype=np.int64),
                "slo_scalars": np.zeros((3,), dtype=np.int64)}

    # -- convenience constructors --------------------------------------
    def register_from_inv(self, resv_inv, weight_inv,
                          limit_inv) -> None:
        """Register every slot from the engine state's inverse-rate
        arrays (closed-population runs: slot == client id; rates are
        re-derived with the timebase's exact inverse so the contract
        the plane prices against is the device truth, not a parallel
        host copy)."""
        from ..core.timebase import NS_PER_SEC

        def to_rate(inv):
            inv = np.asarray(inv, dtype=np.int64)
            with np.errstate(divide="ignore"):
                return np.where(inv > 0, NS_PER_SEC / np.maximum(
                    inv, 1), 0.0)

        r = to_rate(resv_inv)
        w = to_rate(weight_inv)
        l = to_rate(limit_inv)
        for c in range(len(r)):
            self.register(c, float(r[c]), float(w[c]), float(l[c]))


def load_windows_jsonl(path: str) -> List[dict]:
    """Read a ``SloPlane.export_jsonl`` file back (judged or raw rows;
    malformed lines are skipped with a count in row 0's ``_skipped``
    when any -- the offline tool's fail-soft read)."""
    rows: List[dict] = []
    skipped = 0
    with open(path) as fh:
        for ln in fh:
            ln = ln.strip()
            if not ln:
                continue
            try:
                obj = json.loads(ln)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if isinstance(obj, dict):
                rows.append(obj)
            else:
                skipped += 1
    if skipped and rows:
        rows[0] = dict(rows[0], _skipped=skipped)
    return rows
