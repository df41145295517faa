"""Device-side distributed ServiceTracker (the psum delta/rho protocol).

Vectorized equivalent of the host ``core.tracker.ServiceTracker`` with
``OrigTracker`` accounting (reference ``dmclock_client.h:39-84``,
``:157-287``), laid out for a mesh: state is per-(server, client), and
the client's *global* completion counters -- which the host tracker
keeps as plain ints -- become a ``psum`` of per-server counters over the
``servers`` mesh axis.

Per (server s, client c), mirroring OrigTracker's fields:
  ``last_mark``  = global counter value at c's previous request to s
                   (``delta_prev_req``/``rho_prev_req``)
  ``own_since``  = c's completions AT s since that request
                   (``my_delta``/``my_rho``)
so a request from c to s carries
  ``delta_out = global_delta[c] - last_mark[s,c] - own_since[s,c]``
(reference ``prepare_req``, dmclock_client.h:59-67).

Counters start at 1, matching ``GlobalCounters`` (dmclock_client.h:191-198).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class TrackerState(NamedTuple):
    """Per-server shard of the distributed tracker ([C] arrays local to
    one server; stack/shard a leading ``servers`` axis for a cluster)."""

    completed_delta: jnp.ndarray  # int64[C] completions served here, by client
    completed_rho: jnp.ndarray    # int64[C] reservation-phase subset
    last_mark_delta: jnp.ndarray  # int64[C] global delta at last request here
    last_mark_rho: jnp.ndarray    # int64[C]
    seen: jnp.ndarray             # bool[C] client has contacted this server


def init_tracker(n_clients: int) -> TrackerState:
    z = jnp.zeros((n_clients,), dtype=jnp.int64)
    return TrackerState(
        completed_delta=z, completed_rho=z,
        last_mark_delta=z, last_mark_rho=z,
        seen=jnp.zeros((n_clients,), dtype=bool),
    )


def global_counters(tracker: TrackerState, psum):
    """The client-global counters: psum of per-server completions over
    the mesh, plus the reference's start-at-1 offset.

    ``psum`` is the collective to use -- ``lambda x: lax.psum(x,
    'servers')`` inside shard_map, or a plain sum for unsharded use.
    """
    return 1 + psum(tracker.completed_delta), \
        1 + psum(tracker.completed_rho)


def tracker_track(tracker: TrackerState, slots: jnp.ndarray,
                  costs: jnp.ndarray, phases: jnp.ndarray,
                  served: jnp.ndarray) -> TrackerState:
    """Fold a batch of completions at THIS server into the counters
    (reference resp_update, dmclock_client.h:69-79): delta always, rho
    only for reservation-phase service.

    slots/costs/phases/served are the decision-stream arrays from
    ``engine_run`` (phase 0 = reservation).
    """
    idx = jnp.where(served, slots, 0)
    add = jnp.where(served, costs, 0)
    add_rho = jnp.where(served & (phases == 0), costs, 0)
    return tracker._replace(
        completed_delta=tracker.completed_delta.at[idx].add(add),
        completed_rho=tracker.completed_rho.at[idx].add(add_rho),
    )


def tracker_track_counts(tracker: TrackerState, served: jnp.ndarray,
                         served_resv: jnp.ndarray,
                         cost: jnp.ndarray) -> TrackerState:
    """Counts form of :func:`tracker_track` for engines that emit
    per-client completion totals instead of an ordered decision stream
    (the calendar engine's ``served``/``served_resv`` vectors):
    ``delta += served * cost``, ``rho += served_resv * cost`` -- the
    exact sums the per-decision fold computes when every request of a
    client carries the same cost (the device sim's model; ``cost`` is
    the per-client [C] request cost).  Dense adds, no scatter."""
    return tracker._replace(
        completed_delta=tracker.completed_delta
        + served.astype(jnp.int64) * cost,
        completed_rho=tracker.completed_rho
        + served_resv.astype(jnp.int64) * cost,
    )


def tracker_prepare(tracker: TrackerState, requesting: jnp.ndarray,
                    global_delta: jnp.ndarray, global_rho: jnp.ndarray):
    """ReqParams for every client in ``requesting`` (bool[C]) sending its
    next request to THIS server (reference prepare_req + the first-
    contact ReqParams(1,1) case, dmclock_client.h:241-251).

    Returns (new_tracker, delta_out[C], rho_out[C]) with outputs valid
    where ``requesting``.
    """
    # OrigTracker's algebra: delta_out = (global movement since the
    # previous request here) - (own completions here since then), i.e.
    #   delta_out = (global - global_mark) - (own - own_mark).
    # One stored field suffices: last_mark_delta keeps
    # ``global_mark - own_mark``, so
    #   delta_out = global - completed - last_mark_delta
    # and re-marking stores ``global - completed`` again.
    mark = tracker.last_mark_delta
    mark_rho = tracker.last_mark_rho
    delta_out = jnp.where(
        tracker.seen,
        global_delta - tracker.completed_delta - mark,
        1)
    rho_out = jnp.where(
        tracker.seen,
        global_rho - tracker.completed_rho - mark_rho,
        1)
    new_mark = jnp.where(requesting,
                         global_delta - tracker.completed_delta, mark)
    new_mark_rho = jnp.where(requesting,
                             global_rho - tracker.completed_rho, mark_rho)
    tracker = tracker._replace(
        last_mark_delta=new_mark,
        last_mark_rho=new_mark_rho,
        seen=tracker.seen | requesting,
    )
    return tracker, delta_out, rho_out


def global_counters_from(completed_delta: jnp.ndarray,
                         completed_rho: jnp.ndarray, psum):
    """:func:`global_counters` over RAW per-client completion-count
    arrays (the mesh serving plane's counter plane keeps ``int64[C]``
    arrays instead of a full ``TrackerState`` -- its per-shard engines
    ingest unit-rate superwaves, so only the completions half of the
    protocol is live).  Same start-at-1 origin, same collective."""
    return 1 + psum(completed_delta), 1 + psum(completed_rho)


def counter_view_bytes(n_clients: int) -> int:
    """Wire bytes of ONE counter-view exchange: the [C]-sized
    delta + rho int64 psum -- the paper's per-request four-scalar
    piggyback contract, batched into one collective.  This is the
    number the mesh bench records as ``counter_bytes_per_sync``."""
    return 2 * 8 * int(n_clients)


def exchange_schedule(epochs: int, counter_sync_every: int,
                      start: int = 0) -> dict:
    """Host-side accounting of the mesh plane's batched counter
    exchange over the ``epochs`` boundaries starting at GLOBAL epoch
    ``start`` with the ``counter_sync_every`` staleness knob (the
    device grid is ``epoch % K == 0``, so epoch 0 always syncs):
    sync count and cadence -- multiply by :func:`counter_view_bytes`
    for the wire totals in the MULTICHIP v2 record.  ``start``
    matters whenever a measured window begins off-grid (a timed
    window that starts after warmup; ``run_mesh_rounds``'s
    ``round0`` is the same anchor)."""
    every = max(int(counter_sync_every), 1)
    e0 = int(start)
    n = max(int(epochs), 0)
    first = -(-e0 // every) * every       # first sync epoch >= e0
    syncs = len(range(first, e0 + n, every))
    return {"epochs": n, "counter_sync_every": every,
            "start": e0, "syncs": syncs,
            "sync_frac": syncs / max(n, 1)}


# ----------------------------------------------------------------------
# observability (obs.registry wiring)
# ----------------------------------------------------------------------

def tracker_snapshot(tracker) -> dict:
    """Aggregate hot-path stats of one tracker shard as host scalars
    (works for both ``TrackerState`` and ``BorrowTrackerState``).  One
    device fetch per call -- drain-time only, never per request."""
    import numpy as np

    out = {
        "completed_delta_total": int(np.asarray(
            tracker.completed_delta).sum()),
        "completed_rho_total": int(np.asarray(
            tracker.completed_rho).sum()),
        "clients_seen": int(np.asarray(tracker.seen).sum()),
    }
    if hasattr(tracker, "borrow_delta"):
        out["borrow_delta_outstanding"] = int(np.asarray(
            tracker.borrow_delta).sum())
        out["borrow_rho_outstanding"] = int(np.asarray(
            tracker.borrow_rho).sum())
    return out


def register_tracker_metrics(registry, get_tracker, labels=None) -> None:
    """Register callback gauges over a tracker shard.  ``get_tracker``
    returns the CURRENT state (tracker states are immutable NamedTuples
    that callers rebind, so a getter is the only stable handle)."""
    def gauge_fn(key):
        return lambda: tracker_snapshot(get_tracker()).get(key, 0)

    for key in ("completed_delta_total", "completed_rho_total",
                "clients_seen"):
        registry.gauge(f"dmclock_tracker_{key}",
                       "distributed ServiceTracker shard stat",
                       labels=labels).set_function(gauge_fn(key))


# ----------------------------------------------------------------------
# BorrowingTracker variant (reference dmclock_client.h:90-154)
# ----------------------------------------------------------------------

class BorrowTrackerState(NamedTuple):
    """Per-server shard of the distributed BorrowingTracker: guarantees
    delta/rho >= 1 by borrowing future replies (reference
    calc_with_borrow, dmclock_client.h:110-129)."""

    completed_delta: jnp.ndarray  # int64[C] completions served here
    completed_rho: jnp.ndarray    # int64[C] reservation-phase subset
    prev_delta: jnp.ndarray       # int64[C] global delta at last request here
    prev_rho: jnp.ndarray         # int64[C]
    borrow_delta: jnp.ndarray     # int64[C] outstanding borrow
    borrow_rho: jnp.ndarray       # int64[C]
    seen: jnp.ndarray             # bool[C]


def init_borrow_tracker(n_clients: int) -> BorrowTrackerState:
    z = jnp.zeros((n_clients,), dtype=jnp.int64)
    return BorrowTrackerState(
        completed_delta=z, completed_rho=z,
        prev_delta=z, prev_rho=z,
        borrow_delta=z, borrow_rho=z,
        seen=jnp.zeros((n_clients,), dtype=bool),
    )


def borrow_tracker_track(tracker: BorrowTrackerState, slots, costs,
                         phases, served) -> BorrowTrackerState:
    """Fold a batch of completions at THIS server (reference
    BorrowingTracker::resp_update, dmclock_client.h:131-141: only the
    global counters move -- the psum source here).  The fold is the
    same completed_delta/completed_rho scatter-add as OrigTracker's."""
    return tracker_track(tracker, slots, costs, phases, served)


def _calc_with_borrow(global_c, prev, borrow):
    """Vector form of calc_with_borrow (dmclock_client.h:110-129)."""
    result = global_c - prev
    out = jnp.where(result == 0, 1,
                    jnp.where(result > borrow, result - borrow, 1))
    new_borrow = jnp.where(result == 0, borrow + 1,
                           jnp.where(result > borrow, 0,
                                     borrow - result + 1))
    return out, new_borrow


def borrow_tracker_prepare(tracker: BorrowTrackerState, requesting,
                           global_delta, global_rho):
    """ReqParams for every client in ``requesting`` sending its next
    request to THIS server (reference prepare_req,
    dmclock_client.h:131-137; first contact returns ReqParams(1,1) and
    installs the marks, :241-251)."""
    d_out, nbd = _calc_with_borrow(global_delta, tracker.prev_delta,
                                   tracker.borrow_delta)
    r_out, nbr = _calc_with_borrow(global_rho, tracker.prev_rho,
                                   tracker.borrow_rho)
    d_out = jnp.where(tracker.seen, d_out, 1)
    r_out = jnp.where(tracker.seen, r_out, 1)
    upd = requesting
    first = upd & ~tracker.seen
    tracker = tracker._replace(
        prev_delta=jnp.where(upd, global_delta, tracker.prev_delta),
        prev_rho=jnp.where(upd, global_rho, tracker.prev_rho),
        borrow_delta=jnp.where(first, 0,
                               jnp.where(upd, nbd,
                                         tracker.borrow_delta)),
        borrow_rho=jnp.where(first, 0,
                             jnp.where(upd, nbr, tracker.borrow_rho)),
        seen=tracker.seen | requesting,
    )
    return tracker, d_out, r_out
