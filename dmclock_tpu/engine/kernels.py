"""Jitted dmClock kernels: tag recurrence, fused selection, batched run.

Device-side equivalents of the reference's hot path, semantics pinned by
the Python oracle (``dmclock_tpu.core.scheduler``) which is itself a
re-implementation of ``/root/reference/src/dmclock_server.h``:

- ``_make_tag``     = RequestTag recurrence / ``tag_calc`` (:145-183, :246-259)
- ``engine_step``   = ``do_next_request`` (:1115-1186) +
                      ``pop_process_request``/``update_next_tag`` (:1021-1073) +
                      ``reduce_reservation_tags`` (:1077-1111),
                      fused into one launch.  The three heap tops become
                      masked lexicographic argmins over the same total
                      order the oracle sorts by (tag, then creation
                      order), which is what makes cross-backend request
                      ordering bit-exact.
- ``engine_run``    = ``lax.scan`` of engine_step: many scheduling
                      decisions per launch (the batching that buys TPU
                      throughput).
- ``ingest``        = ``do_add_request`` (:913-1018) over a scanned op
                      batch, including idle-reactivation prop_delta
                      (:937-985) as a free masked min instead of the
                      reference's O(n) scan.

All arithmetic is int64 ns (see ``core.timebase``).  Everything here is
pure and jittable; config axes (AtLimit, anticipation) are static args
closed over by the queue wrapper's jit instances.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.timebase import (MAX_CHARGE_UNITS, MAX_TAG, MIN_TAG,
                             LOWEST_PROP_TAG_TRIGGER, ORGANIC_TAG_CAP,
                             TIME_MAX)
from .state import EngineState

# Masking sentinel for argmin keys: strictly above every legal key
# (tags are <= MAX_TAG = 2^62; effective proportions reach ~1.5*2^62).
KEY_INF = (1 << 63) - 1

# Decision type codes (== core.scheduler.NextReqType values)
RETURNING = 0
FUTURE = 1
NONE = 2


class Decision(NamedTuple):
    """One scheduling decision, device-side (oracle: NextReq/PullReq)."""

    type: jnp.ndarray         # int32: RETURNING/FUTURE/NONE
    slot: jnp.ndarray         # int32: winning client slot (-1 if none)
    phase: jnp.ndarray        # int32: 0 reservation, 1 priority
    cost: jnp.ndarray         # int64: served request cost
    when: jnp.ndarray         # int64: FUTURE wake-up time (ns)
    limit_break: jnp.ndarray  # bool: served via AtLimit::Allow fallback


# ----------------------------------------------------------------------
# tag algebra (vector form of core.tags)
# ----------------------------------------------------------------------

def _tag_axis(time_ns, prev, inv, dist, extreme_is_high: bool, cost):
    """One tag axis (reference tag_calc, dmclock_server.h:246-259)."""
    units = jnp.minimum(dist + cost, MAX_CHARGE_UNITS)
    organic = jnp.minimum(jnp.maximum(time_ns, prev + inv * units),
                          ORGANIC_TAG_CAP)
    sentinel = MAX_TAG if extreme_is_high else MIN_TAG
    return jnp.where(inv == 0, sentinel, organic)


def _make_tag(prev_r, prev_p, prev_l, prev_arrival,
              r_inv, w_inv, l_inv, delta, rho, time_ns, cost,
              anticipation_ns: int):
    """The RequestTag recurrence (reference :145-183): reservation uses
    rho, proportion/limit use delta; anticipation backdates arrivals
    within the window of the previous arrival (:159-161)."""
    backdate = (time_ns - anticipation_ns) < prev_arrival
    max_time = jnp.where(backdate, time_ns - anticipation_ns, time_ns)
    r = _tag_axis(max_time, prev_r, r_inv, rho, True, cost)
    p = _tag_axis(max_time, prev_p, w_inv, delta, True, cost)
    l = _tag_axis(max_time, prev_l, l_inv, delta, False, cost)
    return r, p, l


def _fold_prev(prev, tag):
    """prev_tag update skips pinned sentinels
    (oracle ClientRec.update_req_tag; reference :399-412)."""
    pinned = (tag == MAX_TAG) | (tag == MIN_TAG)
    return jnp.where(pinned, prev, tag)


def _min_not_0(current, possible):
    """min where 0 means "no time" (reference min_not_0_time :1192-1195)."""
    return jnp.where(possible == 0, current,
                     jnp.minimum(current, possible))


# ----------------------------------------------------------------------
# int32 epoch tag rebase
# ----------------------------------------------------------------------
#
# The epoch scans carry ~10 int64 [N] tag/arrival/cost arrays through
# every iteration; at bench shapes that loop-carried traffic is the
# bulk of the remaining elementwise cost (seen on the retired remote TPU
# runtime; not chip evidence).
# Within one epoch the organic values of each field move only a few ms
# of virtual time, so they fit an int32 offset from a per-field origin
# with ~2.1s (+-2^31 ns) to spare.  ``rebase32``/``restore64`` are the
# exact (window-checked) conversion pair: sentinels (MAX_TAG/MIN_TAG,
# which pin tags of disabled QoS axes) map to reserved int32 codes, and
# any organic value outside the window fails the check -- the caller
# must then stay on (fall back to) the int64 path.  Round-trip
# bit-exactness inside the window is pinned by tests/test_radix.py.

I32_MAX_TAG = (1 << 31) - 1     # reserved code for MAX_TAG
I32_MIN_TAG = -(1 << 31)        # reserved code for MIN_TAG
# organic window: strictly inside the reserved codes, with a small
# margin so clamped garbage can never alias a sentinel
_I32_WINDOW = (1 << 31) - 8


def rebase32(vals, origin):
    """Rebase int64 tags to int32 around ``origin``.

    Returns ``(vals32, ok)``: exact sentinel mapping for MAX_TAG /
    MIN_TAG, exact offset for organic values within +-(2^31 - 8) of
    ``origin``; ``ok`` is False when any organic value falls outside
    the window (the conversion result must then be discarded)."""
    is_max = vals == MAX_TAG
    is_min = vals == MIN_TAG
    rel = vals - origin
    in_win = (rel > -_I32_WINDOW) & (rel < _I32_WINDOW)
    ok = jnp.all(is_max | is_min | in_win)
    v32 = jnp.where(
        is_max, jnp.int64(I32_MAX_TAG),
        jnp.where(is_min, jnp.int64(I32_MIN_TAG),
                  jnp.clip(rel, -_I32_WINDOW, _I32_WINDOW)))
    return v32.astype(jnp.int32), ok


def restore64(vals32, origin):
    """Exact inverse of :func:`rebase32` for in-window conversions."""
    v = vals32.astype(jnp.int64)
    return jnp.where(vals32 == I32_MAX_TAG, jnp.int64(MAX_TAG),
                     jnp.where(vals32 == I32_MIN_TAG, jnp.int64(MIN_TAG),
                               v + origin))


# ----------------------------------------------------------------------
# dense-histogram (radix) key selection
# ----------------------------------------------------------------------
#
# Shared machinery: the prefix engine's k-selection
# (``fastpath._select_radix``) and the calendar engine's bucketed
# stop-key ladder both need order statistics of an int64 key vector
# without sorting it.  Multi-pass dense histograms walk the key space
# top-down to the exact kk-th smallest element -- O(N) work per round,
# no sort, no scatter, no scalar gathers (masked reductions only: two
# scalar gathers cost ~0.8 ms per batch on the retired remote TPU
# runtime; not chip evidence).  Digit width: dense one-hot histograms cost
# rounds * 2^bits * N compares = (64/b) * 2^b * N, minimized at small
# b; 4-bit digits (16 rounds of 16-bucket histograms) cost 8x less
# than 8-bit ones and keep every round a pure vectorized
# compare+reduce.

RADIX_BITS = 4
RADIX_SPAN = 1 << RADIX_BITS


def radix_kth_key(pk, kk):
    """Exact value of the ``kk``-th smallest element of ``pk``
    (1-indexed, duplicates counted) via 16 rounds of 4-bit dense
    histograms over the int64 key space.  ``kk`` may be a static int
    or a traced int32 scalar (the calendar quantile ladder passes
    traced CDF ranks).  ``pk`` must be non-negative (packed keys and
    the KEY_INF sentinel both are)."""
    buckets = jnp.arange(RADIX_SPAN, dtype=jnp.int64)
    lanes = jnp.arange(RADIX_SPAN, dtype=jnp.int32)
    prefix = jnp.int64(0)
    remaining = jnp.asarray(kk, dtype=jnp.int32)
    active = jnp.ones(pk.shape, dtype=bool)
    for shift in range(64 - RADIX_BITS, -1, -RADIX_BITS):
        digit = (pk >> shift) & (RADIX_SPAN - 1)
        hist = jnp.sum(active[None, :] & (digit[None, :]
                                          == buckets[:, None]),
                       axis=1, dtype=jnp.int32)
        cum = jnp.cumsum(hist)
        sel = jnp.argmax(cum >= remaining).astype(jnp.int32)
        below = jnp.sum(jnp.where(lanes < sel, hist, 0))
        remaining = remaining - below
        prefix = prefix | (sel.astype(jnp.int64) << shift)
        active = active & (digit == sel.astype(jnp.int64))
    return prefix


def radix_quantile_ladder(pk, levels: int):
    """CDF quantile ladder of the FINITE entries of ``pk``: boundary i
    (1-indexed) is the ``ceil(i * C / levels)``-th smallest key, where
    C counts entries strictly below KEY_INF.  Returns nondecreasing
    int64[levels] (all KEY_INF when nothing is finite).

    This is the calendar engine's bucketed-commit planner
    (docs/ENGINE.md): the per-client stop keys of a measure pass
    histogram into the ladder B_1 <= ... <= B_levels that predicts
    where successive refreshed-budget commit levels will land on a
    skewed stop distribution."""
    fin = jnp.sum((pk < jnp.int64(KEY_INF)).astype(jnp.int32))
    lv = jnp.arange(1, levels + 1, dtype=jnp.int32)
    ranks = jnp.maximum((lv * fin + levels - 1) // levels,
                        jnp.int32(1))
    return jax.vmap(lambda r: radix_kth_key(pk, r))(ranks)


# ----------------------------------------------------------------------
# timer-wheel scatter/scan: bucketed calendars over the key space
# ----------------------------------------------------------------------
#
# The timer-wheel primitives behind the calendar engine's
# calendar_impl="wheel": keys scatter into a fixed grid of buckets
# (count + exact per-bucket minimum), and nearest-deadline is an
# O(buckets) hierarchical occupancy scan (coarse group any-reduction,
# then first-set fine bucket) instead of a dense min over N lanes.
# The exactness argument is one line: ``wheel_slot`` is monotone
# NONDECREASING in the key for ANY origin/shift (out-of-span keys
# clamp to the edge buckets, which preserves monotonicity), so the
# first occupied bucket contains the global masked minimum and its
# stored ``bmin`` -- a scatter-min of the ACTUAL keys, not a bucket
# edge -- IS that minimum, bit for bit.  Geometry therefore only
# affects discrimination (how many keys share the clamp buckets),
# never the result.

WHEEL_GROUPS = 8


def wheel_slot(key, origin, shift: int, nb: int):
    """Bucket index of ``key`` on a wheel of ``nb`` buckets of width
    ``2**shift`` ns starting at ``origin``.  Out-of-span keys clamp to
    the edge buckets (monotone, hence exact -- see section comment)."""
    rel = (key - origin) >> shift
    return jnp.clip(rel, 0, nb - 1).astype(jnp.int32)


def wheel_scatter(keys, slot, nb: int):
    """Scatter ``keys`` into ``nb`` buckets: per-bucket occupancy
    count and exact minimum key.  ``slot == nb`` masks a lane out
    (dropped by the scatter).  Returns ``(cnt int32[nb],
    bmin int64[nb])`` with KEY_INF in empty buckets."""
    cnt = jnp.zeros((nb,), jnp.int32).at[slot].add(
        jnp.int32(1), mode="drop")
    bmin = jnp.full((nb,), jnp.int64(KEY_INF)).at[slot].min(
        keys, mode="drop")
    return cnt, bmin


def wheel_nearest(cnt, bmin, groups: int = WHEEL_GROUPS):
    """O(buckets) nearest-deadline: hierarchical occupancy ffs --
    coarse any-reduction over ``groups`` bucket groups, argmax picks
    the first occupied group, a dynamic slice finds its first occupied
    fine bucket -- then the bucket's stored min.  Returns
    ``(val, b0, found)`` with ``val = KEY_INF`` and ``b0 = nb`` when
    every bucket is empty."""
    nb = cnt.shape[0]
    gw = nb // groups
    occ = cnt > 0
    gany = jnp.any(occ.reshape(groups, gw), axis=1)
    g = jnp.argmax(gany).astype(jnp.int32)
    fine = lax.dynamic_slice(occ, (g * gw,), (gw,))
    b0 = g * gw + jnp.argmax(fine).astype(jnp.int32)
    found = jnp.any(gany)
    val = jnp.where(found, bmin[b0], jnp.int64(KEY_INF))
    return val, jnp.where(found, b0, nb).astype(jnp.int32), found


def wheel_scan(keys, slot, nb: int, *, groups: int = WHEEL_GROUPS):
    """Fused bucket-scatter + occupancy-min-scan: one pass from lanes
    to ``(cnt, bmin, nearest, found)``.  This is the XLA reference of
    the Pallas kernel in :mod:`engine.kernels_pallas`; the two are
    bit-identical (ci.sh wheel smoke, interpret mode on CPU)."""
    cnt, bmin = wheel_scatter(keys, slot, nb)
    val, _b0, found = wheel_nearest(cnt, bmin, groups)
    return cnt, bmin, val, found


# ----------------------------------------------------------------------
# selection: masked lexicographic argmin = a heap top
# ----------------------------------------------------------------------

def _masked_argmin(mask, key, order):
    """Top of a 'heap' ordered by (mask desc, key asc, order asc).

    Returns (valid, index, min_key).  Two-stage: min key among mask,
    then min creation order among key-ties -- the oracle's exact
    tie-break, so selection is deterministic and backend-independent.
    """
    k = jnp.where(mask, key, KEY_INF)
    min_key = jnp.min(k)
    tie = k == min_key
    idx = jnp.argmin(jnp.where(tie, order, KEY_INF)).astype(jnp.int32)
    return jnp.any(mask), idx, min_key


# ----------------------------------------------------------------------
# one scheduling decision (fused select + pop + retag)
# ----------------------------------------------------------------------

def engine_step(state: EngineState, now: jnp.ndarray, *,
                allow_limit_break: bool,
                anticipation_ns: int):
    """One ``do_next_request`` + serve, fully on device.

    Mirrors the oracle's decision order exactly: reservation phase,
    ready promotion, weight phase, optional Allow limit-break, else
    future/none (reference :1115-1186).
    """
    has_req = state.active & (state.depth > 0)
    eff_prop = state.head_prop + state.prop_delta

    # --- reservation heap top; constraint phase (:1124-1128)
    resv_valid, resv_idx, resv_min = _masked_argmin(
        has_req, state.head_resv, state.order)
    serve_resv = resv_valid & (resv_min <= now)

    # --- promote newly within-limit heads to ready (:1135-1144);
    # the oracle's promote loop marks exactly {head.limit <= now}, which
    # here is one mask op.  Gated on the reservation phase NOT serving:
    # the oracle returns before the promote loop in that case, and the
    # ready flags are persistent state, so promoting early would diverge
    # under non-monotonic injected pull times.
    head_ready = jnp.where(
        serve_resv, state.head_ready,
        state.head_ready | (has_req & ~state.head_ready &
                            (state.head_limit <= now)))

    # --- ready heap top; weight phase (:1146-1151)
    ready_mask = has_req & head_ready
    rdy_valid, rdy_idx, _ = _masked_argmin(ready_mask, eff_prop,
                                           state.order)
    serve_ready = (~serve_resv) & rdy_valid & \
        (state.head_prop[rdy_idx] < MAX_TAG)

    # --- overall ready-heap top (ready clients sort before non-ready:
    # oracle _ready_key) -- needed for the Allow fallback (:1157-1165)
    nonready_mask = has_req & ~head_ready
    nr_valid, nr_idx, _ = _masked_argmin(nonready_mask, eff_prop,
                                         state.order)
    overall_idx = jnp.where(rdy_valid, rdy_idx, nr_idx)
    overall_valid = rdy_valid | nr_valid
    if allow_limit_break:
        undecided = ~serve_resv & ~serve_ready
        lb_ready_ok = overall_valid & \
            (state.head_prop[overall_idx] < MAX_TAG)
        lb_serve_ready = undecided & lb_ready_ok
        lb_serve_resv = undecided & ~lb_ready_ok & resv_valid & \
            (resv_min < MAX_TAG)
    else:
        lb_serve_ready = jnp.bool_(False)
        lb_serve_resv = jnp.bool_(False)

    # --- nothing eligible: earliest future time (:1170-1185).  The
    # limit-heap top orders non-ready before ready (oracle _limit_key).
    l_nr_valid, l_nr_idx, _ = _masked_argmin(
        nonready_mask, state.head_limit, state.order)
    l_r_valid, l_r_idx, _ = _masked_argmin(
        ready_mask, state.head_limit, state.order)
    lim_idx = jnp.where(l_nr_valid, l_nr_idx, l_r_idx)
    lim_valid = l_nr_valid | l_r_valid
    next_call = jnp.int64(TIME_MAX)
    next_call = jnp.where(resv_valid, _min_not_0(next_call, resv_min),
                          next_call)
    next_call = jnp.where(lim_valid,
                          _min_not_0(next_call, state.head_limit[lim_idx]),
                          next_call)

    serving = serve_resv | serve_ready | lb_serve_ready | lb_serve_resv
    phase_is_ready = serve_ready | lb_serve_ready
    w = jnp.where(serve_resv | lb_serve_resv, resv_idx, overall_idx)
    limit_break = lb_serve_ready | lb_serve_resv

    # ------------------------------------------------------------------
    # serve winner w (pop_process_request :1046-1073 + update_next_tag
    # :1021-1036 + reduce_reservation_tags :1077-1111)
    # ------------------------------------------------------------------
    served_r = state.head_resv[w]
    served_p = state.head_prop[w]
    served_l = state.head_limit[w]
    served_arr = state.head_arrival[w]
    served_cost = state.head_cost[w]
    served_rho = state.head_rho[w]

    new_depth = state.depth[w] - 1
    has_more = new_depth > 0

    # pop the oldest tail element as the new head
    rq = state.q_head[w]
    narr = state.q_arrival[w, rq]
    ncost = state.q_cost[w, rq]

    # delayed tagging of the new head: recurrence predecessor is the
    # just-served tag, with the client's latest rho/delta (:1021-1036)
    nr_tag, np_tag, nl_tag = _make_tag(
        served_r, served_p, served_l, served_arr,
        state.resv_inv[w], state.weight_inv[w], state.limit_inv[w],
        state.cur_delta[w], state.cur_rho[w], narr, ncost,
        anticipation_ns)

    # weight-phase service pays reservation debt (:1077-1111); under
    # delayed calc only the head (here: the freshly-tagged new head)
    # and prev_tag are adjusted
    offset = jnp.where(phase_is_ready,
                       state.resv_inv[w] * (served_cost + served_rho),
                       jnp.int64(0))

    # prev_tag folds in the new head tag (update_req_tag), then the
    # reservation offset -- matching the oracle's operation order
    new_prev_r = jnp.where(has_more,
                           _fold_prev(state.prev_resv[w], nr_tag),
                           state.prev_resv[w]) - offset
    new_prev_p = jnp.where(has_more,
                           _fold_prev(state.prev_prop[w], np_tag),
                           state.prev_prop[w])
    new_prev_l = jnp.where(has_more,
                           _fold_prev(state.prev_limit[w], nl_tag),
                           state.prev_limit[w])
    new_prev_arr = jnp.where(has_more, narr, state.prev_arrival[w])

    def upd(arr, value, pred):
        return arr.at[w].set(jnp.where(serving & pred, value, arr[w]))

    true1 = jnp.bool_(True)
    state = state._replace(
        depth=upd(state.depth, new_depth.astype(jnp.int32), true1),
        q_head=upd(state.q_head,
                   ((rq + 1) % state.ring_capacity).astype(jnp.int32),
                   has_more),
        head_resv=upd(state.head_resv, nr_tag - offset, has_more),
        head_prop=upd(state.head_prop, np_tag, has_more),
        head_limit=upd(state.head_limit, nl_tag, has_more),
        head_arrival=upd(state.head_arrival, narr, has_more),
        head_cost=upd(state.head_cost, ncost, has_more),
        head_rho=upd(state.head_rho, state.cur_rho[w], has_more),
        head_ready=head_ready.at[w].set(
            jnp.where(serving, False, head_ready[w])),
        prev_resv=upd(state.prev_resv, new_prev_r, true1),
        prev_prop=upd(state.prev_prop, new_prev_p, true1),
        prev_limit=upd(state.prev_limit, new_prev_l, true1),
        prev_arrival=upd(state.prev_arrival, new_prev_arr, true1),
    )

    decision = Decision(
        type=jnp.where(serving, RETURNING,
                       jnp.where(next_call < TIME_MAX, FUTURE,
                                 NONE)).astype(jnp.int32),
        slot=jnp.where(serving, w, -1).astype(jnp.int32),
        phase=phase_is_ready.astype(jnp.int32),
        cost=jnp.where(serving, served_cost, 0),
        when=next_call,
        limit_break=jnp.asarray(limit_break, dtype=bool),
    )
    return state, decision


def engine_run(state: EngineState, now: jnp.ndarray, steps: int, *,
               allow_limit_break: bool, anticipation_ns: int,
               advance_now: bool = False, with_horizon: bool = False,
               with_metrics: bool = False):
    """``steps`` scheduling decisions in one launch via lax.scan.

    With a fixed ``now`` this equals ``steps`` successive pulls at the
    same instant (once a FUTURE/NONE occurs, state is unchanged and all
    later decisions repeat it).  With ``advance_now`` the virtual clock
    jumps to each FUTURE's wake-up time -- an infinitely-fast server,
    which is the decisions/sec benchmark mode.

    With ``with_horizon`` a 4th value is returned: the earliest
    reservation or (non-ready) limit tag STRICTLY past ``now`` present
    in any intermediate state of the run.  Decisions depend on ``now``
    only through the threshold tests ``resv <= now`` and ``limit <=
    now`` (reference do_next_request :1115-1186), so for any t in
    [now, horizon) this exact decision sequence is what pulls at t
    would also have produced -- the validity window for speculative
    decision buffers.  Conservative: tags replaced mid-run count via
    the initial-state minimum, created tags via per-step minima.

    With ``with_metrics`` an ``obs.device`` metrics vector is appended
    to the return tuple, accumulated in the same scan (phase counts,
    limit-capped FUTURE stalls, ring-occupancy high-water mark) and
    drained with the same fetch as the decisions -- no extra launch.
    The flag is STATIC and touches only the metrics carry: the decision
    stream and final state are bit-identical either way (pinned by
    tests/test_obs.py).
    """
    from ..obs import device as _obsdev

    def tag_horizon(st, t):
        has_req = st.active & (st.depth > 0)
        hr = jnp.min(jnp.where(has_req & (st.head_resv > t),
                               st.head_resv, TIME_MAX))
        nonready = has_req & ~st.head_ready & (st.head_limit > t)
        hl = jnp.min(jnp.where(nonready, st.head_limit, TIME_MAX))
        return jnp.minimum(hr, hl)

    def body(carry, _):
        st, t, h, met = carry
        st, dec = engine_step(st, t,
                              allow_limit_break=allow_limit_break,
                              anticipation_ns=anticipation_ns)
        if with_horizon:
            # the served client's freshly-created head tags are the only
            # tags not present in the PREVIOUS state; fold them in
            w = jnp.maximum(dec.slot, 0)
            nr = st.head_resv[w]
            nl = st.head_limit[w]
            served = dec.slot >= 0
            h = jnp.where(served & (nr > t), jnp.minimum(h, nr), h)
            h = jnp.where(served & ~st.head_ready[w] & (nl > t),
                          jnp.minimum(h, nl), h)
        if with_metrics:
            served1 = (dec.type == RETURNING).astype(jnp.int64)
            is_resv = served1 * (dec.phase == 0)
            met = _obsdev.metrics_combine(met, _obsdev.metrics_delta(
                decisions=served1, resv=is_resv,
                prop=served1 - is_resv,
                limit_break=dec.limit_break.astype(jnp.int64),
                stalls=(dec.type == FUTURE).astype(jnp.int64),
                ring_hwm=jnp.max(st.depth).astype(jnp.int64)))
        if advance_now:
            t = jnp.where(dec.type == FUTURE, dec.when, t)
        return (st, t, h, met), dec

    h0 = tag_horizon(state, now) if with_horizon \
        else jnp.int64(TIME_MAX)
    (state, now, horizon, metrics), decisions = lax.scan(
        body, (state, now, h0, _obsdev.metrics_zero()), None,
        length=steps)
    out = (state, now, decisions)
    if with_horizon:
        out = out + (horizon,)
    if with_metrics:
        out = out + (metrics,)
    return out


# ----------------------------------------------------------------------
# ingest: batched do_add_request (+ client creation)
# ----------------------------------------------------------------------

OP_NOP = 0
OP_ADD = 1
OP_CREATE = 2


class IngestOps(NamedTuple):
    """A scanned batch of queue mutations (host-built, padded with NOPs
    so batch shapes hit a few jit cache entries)."""

    kind: jnp.ndarray     # int32[B]: OP_NOP/OP_ADD/OP_CREATE
    slot: jnp.ndarray     # int32[B]
    time: jnp.ndarray     # int64[B] arrival ns (ADD)
    cost: jnp.ndarray     # int64[B]
    rho: jnp.ndarray      # int64[B]
    delta: jnp.ndarray    # int64[B]
    resv_inv: jnp.ndarray   # int64[B] (CREATE)
    weight_inv: jnp.ndarray  # int64[B]
    limit_inv: jnp.ndarray   # int64[B]
    order: jnp.ndarray    # int64[B] creation index (CREATE)


def ingest(state: EngineState, ops: IngestOps, *,
           anticipation_ns: int) -> EngineState:
    """Apply a batch of creates/adds in order (scan), equivalent to the
    oracle's per-call ``_do_add_request`` (reference :913-1018).

    Sequencing matters: a batch may hold several ops for one client, and
    idle-reactivation reads all other clients' state at its moment.
    """

    def body(st: EngineState, op):
        s = op.slot
        is_add = op.kind == OP_ADD
        is_create = op.kind == OP_CREATE

        # ---- CREATE: install a fresh ClientRec (reference :920-932)
        def cset(arr, value):
            return arr.at[s].set(jnp.where(is_create, value, arr[s]))

        st = st._replace(
            active=cset(st.active, True),
            idle=cset(st.idle, True),
            order=cset(st.order, op.order),
            resv_inv=cset(st.resv_inv, op.resv_inv),
            weight_inv=cset(st.weight_inv, op.weight_inv),
            limit_inv=cset(st.limit_inv, op.limit_inv),
            prop_delta=cset(st.prop_delta, 0),
            prev_resv=cset(st.prev_resv, 0),
            prev_prop=cset(st.prev_prop, 0),
            prev_limit=cset(st.prev_limit, 0),
            prev_arrival=cset(st.prev_arrival, 0),
            cur_rho=cset(st.cur_rho, 1),
            cur_delta=cset(st.cur_delta, 1),
            depth=cset(st.depth, 0),
            q_head=cset(st.q_head, 0),
            head_ready=cset(st.head_ready, False),
        )

        # ---- ADD (reference do_add_request :913-1018)
        # idle reactivation (:937-985): lowest effective proportion tag
        # among other non-idle clients, as a masked min (the adding
        # client is still marked idle here, excluding itself -- same as
        # the oracle's scan)
        others = st.active & ~st.idle
        eff = jnp.where(st.depth > 0, st.head_prop, st.prev_prop) \
            + st.prop_delta
        lowest = jnp.min(jnp.where(others, eff, KEY_INF))
        do_shift = is_add & st.idle[s] & jnp.any(others) & \
            (lowest < LOWEST_PROP_TAG_TRIGGER)
        st = st._replace(
            prop_delta=st.prop_delta.at[s].set(
                jnp.where(do_shift, lowest - op.time, st.prop_delta[s])),
            idle=st.idle.at[s].set(jnp.where(is_add, False, st.idle[s])),
        )

        # delayed tagging (:878-893): a real tag only if the request
        # lands at the queue head
        empty = st.depth[s] == 0
        tag_it = is_add & empty
        r, p, l = _make_tag(
            st.prev_resv[s], st.prev_prop[s], st.prev_limit[s],
            st.prev_arrival[s],
            st.resv_inv[s], st.weight_inv[s], st.limit_inv[s],
            op.delta, op.rho, op.time, op.cost, anticipation_ns)

        def hset(arr, value, pred=tag_it):
            return arr.at[s].set(jnp.where(pred, value, arr[s]))

        # tail ring write position (depth includes head; tail count is
        # depth-1, so the new element lands at q_head + depth - 1)
        wpos = (st.q_head[s] + st.depth[s] - 1) % st.ring_capacity
        push_it = is_add & ~empty

        st = st._replace(
            head_resv=hset(st.head_resv, r),
            head_prop=hset(st.head_prop, p),
            head_limit=hset(st.head_limit, l),
            head_arrival=hset(st.head_arrival, op.time),
            head_cost=hset(st.head_cost, op.cost),
            head_rho=hset(st.head_rho, op.rho),
            head_ready=hset(st.head_ready, False),
            prev_resv=hset(st.prev_resv, _fold_prev(st.prev_resv[s], r)),
            prev_prop=hset(st.prev_prop, _fold_prev(st.prev_prop[s], p)),
            prev_limit=hset(st.prev_limit,
                            _fold_prev(st.prev_limit[s], l)),
            prev_arrival=hset(st.prev_arrival, op.time),
            q_arrival=st.q_arrival.at[s, wpos].set(
                jnp.where(push_it, op.time, st.q_arrival[s, wpos])),
            q_cost=st.q_cost.at[s, wpos].set(
                jnp.where(push_it, op.cost, st.q_cost[s, wpos])),
            depth=st.depth.at[s].set(
                jnp.where(is_add, st.depth[s] + 1, st.depth[s])),
            cur_rho=hset(st.cur_rho, op.rho, is_add),
            cur_delta=hset(st.cur_delta, op.delta, is_add),
        )
        return st, None

    state, _ = lax.scan(body, state, ops)
    return state


def ingest_wave(state: EngineState, requesting: jnp.ndarray,
                time_ns, cost: jnp.ndarray, rho: jnp.ndarray,
                delta: jnp.ndarray, *,
                anticipation_ns: int) -> EngineState:
    """Vectorized do_add_request for a WAVE: at most one new request per
    client, all slots distinct, applied in parallel.

    Semantics differ from the sequential ``ingest`` scan in exactly one
    place, by design: idle-reactivation's lowest-proportion-tag scan
    (reference :960-983) reads the PRE-wave state, so a reactivating
    client misses EVERY earlier same-wave op's effect on the scanned
    tags -- other reactivations AND plain adds that retag a drained
    lower-slot client's head.  (Bit-for-bit parity with the scan holds
    when each wave's reactivator, if any, is the wave's lowest slot --
    pinned by tests/test_tpu_engine.py.)  This is the batch-synchronous
    model of ``sim.device_sim``: same-instant arrivals are unordered.
    Everything else -- delayed tagging, ring append, cur rho/delta --
    matches the scan bit for bit.

    ``requesting`` bool[N]; time_ns scalar; cost/rho/delta int64[N].
    """
    st = state
    n = st.capacity

    # --- idle reactivation vs pre-wave state
    others = st.active & ~st.idle
    eff = jnp.where(st.depth > 0, st.head_prop, st.prev_prop) \
        + st.prop_delta
    lowest = jnp.min(jnp.where(others, eff, KEY_INF))
    do_shift = requesting & st.idle & jnp.any(others) & \
        (lowest < LOWEST_PROP_TAG_TRIGGER)
    prop_delta = jnp.where(do_shift, lowest - time_ns, st.prop_delta)
    idle = st.idle & ~requesting

    # --- delayed tagging: a real tag only when the queue is empty
    empty = st.depth == 0
    tag_it = requesting & empty
    t_arr = jnp.full((n,), time_ns, dtype=jnp.int64) \
        if jnp.ndim(time_ns) == 0 else time_ns
    r, p, l = _make_tag(
        st.prev_resv, st.prev_prop, st.prev_limit, st.prev_arrival,
        st.resv_inv, st.weight_inv, st.limit_inv,
        delta, rho, t_arr, cost, anticipation_ns)

    def hset(new, old, pred=tag_it):
        return jnp.where(pred, new, old)

    # --- ring append for non-empty queues: dense one-hot write along
    # the ring axis (per-row scatters serialize on TPU)
    push_it = requesting & ~empty
    wpos = (st.q_head + st.depth - 1) % st.ring_capacity
    onehot = jnp.arange(st.ring_capacity,
                        dtype=jnp.int32)[None, :] == wpos[:, None]
    write = push_it[:, None] & onehot
    q_arrival = jnp.where(write, t_arr[:, None], st.q_arrival)
    q_cost = jnp.where(write, cost[:, None], st.q_cost)

    return st._replace(
        idle=idle,
        prop_delta=prop_delta,
        head_resv=hset(r, st.head_resv),
        head_prop=hset(p, st.head_prop),
        head_limit=hset(l, st.head_limit),
        head_arrival=hset(t_arr, st.head_arrival),
        head_cost=hset(cost, st.head_cost),
        head_rho=hset(rho, st.head_rho),
        head_ready=st.head_ready & ~tag_it,
        prev_resv=hset(_fold_prev(st.prev_resv, r), st.prev_resv),
        prev_prop=hset(_fold_prev(st.prev_prop, p), st.prev_prop),
        prev_limit=hset(_fold_prev(st.prev_limit, l), st.prev_limit),
        prev_arrival=hset(t_arr, st.prev_arrival),
        q_arrival=q_arrival,
        q_cost=q_cost,
        depth=jnp.where(requesting, st.depth + 1,
                        st.depth).astype(jnp.int32),
        cur_rho=hset(rho, st.cur_rho, requesting),
        cur_delta=hset(delta, st.cur_delta, requesting),
    )


def ingest_superwave(state: EngineState, counts: jnp.ndarray,
                     wave_times: jnp.ndarray, cost: jnp.ndarray,
                     rho: jnp.ndarray, delta: jnp.ndarray, *,
                     anticipation_ns: int) -> EngineState:
    """W consecutive ingest waves fused into ONE ring pass.

    Client ``i`` receives ``counts[i]`` arrivals (0 <= counts <= W) at
    times ``wave_times[0..counts[i]-1]``, each with the client's
    ``cost``/``rho``/``delta`` (constant across the superwave).
    Bit-equivalent to W sequential ``ingest_wave`` calls with
    ``requesting_w = counts > w`` (pinned by tests) -- the reactivation
    scan only ever fires at wave 0 (a client with counts > w was
    already non-idle by wave w >= 1), and with no serves in between the
    w-th arrival's ring slot is just ``base + w``.  The point: the
    [N, Q] ring pair is read+written ONCE for the whole superwave
    instead of once per wave, which is what makes sustained
    ingest+serve loops affordable (the reference pays its `add_request`
    cost per call under one mutex, `dmclock_server.h:913-1018`).

    Caller contract: ``depth + counts <= ring capacity`` (same
    no-overflow contract as the other ingest paths) and
    ``wave_times`` ascending with ``len(wave_times) = W`` static.
    """
    st = state
    n = st.capacity
    q = st.ring_capacity
    w_waves = wave_times.shape[0]
    requesting = counts > 0
    t0 = jnp.broadcast_to(wave_times[0], (n,))

    # --- idle reactivation at wave 0, vs pre-superwave state (the
    # ingest_wave batch-synchronous semantics, kernels.ingest_wave)
    others = st.active & ~st.idle
    eff = jnp.where(st.depth > 0, st.head_prop, st.prev_prop) \
        + st.prop_delta
    lowest = jnp.min(jnp.where(others, eff, KEY_INF))
    do_shift = requesting & st.idle & jnp.any(others) & \
        (lowest < LOWEST_PROP_TAG_TRIGGER)
    prop_delta = jnp.where(do_shift, lowest - t0, st.prop_delta)
    idle = st.idle & ~requesting

    # --- wave-0 arrival becomes the head of an empty queue
    empty = st.depth == 0
    tag_it = requesting & empty
    r, p, l = _make_tag(
        st.prev_resv, st.prev_prop, st.prev_limit, st.prev_arrival,
        st.resv_inv, st.weight_inv, st.limit_inv,
        delta, rho, t0, cost, anticipation_ns)

    def hset(new, old, pred=tag_it):
        return jnp.where(pred, new, old)

    # --- ring multi-append: arrivals h..counts-1 land at consecutive
    # ring positions starting at base (h = 1 when the head consumed
    # wave 0).  Dense: for ring column c, the wave index is
    # (c - base) mod Q + h, written when < counts.
    h = tag_it.astype(jnp.int32)
    ring_count = jnp.maximum(counts.astype(jnp.int32) - h, 0)
    base = jnp.remainder(st.q_head + st.depth + h - 1, q)
    col = jnp.arange(q, dtype=jnp.int32)
    jrel = jnp.remainder(col[None, :] - base[:, None], q)
    writem = jrel < ring_count[:, None]
    widx = jrel + h[:, None]
    # wave_times select: W is small and static, so unrolled selects
    # fuse into the single ring pass (a gather would serialize)
    val = jnp.broadcast_to(wave_times[0], (n, q))
    for wv in range(1, w_waves):
        val = jnp.where(widx == wv, wave_times[wv], val)
    q_arrival = jnp.where(writem, val, st.q_arrival)
    q_cost = jnp.where(writem, cost[:, None], st.q_cost)

    return st._replace(
        idle=idle,
        prop_delta=prop_delta,
        head_resv=hset(r, st.head_resv),
        head_prop=hset(p, st.head_prop),
        head_limit=hset(l, st.head_limit),
        head_arrival=hset(t0, st.head_arrival),
        head_cost=hset(cost, st.head_cost),
        head_rho=hset(rho, st.head_rho),
        head_ready=st.head_ready & ~tag_it,
        prev_resv=hset(_fold_prev(st.prev_resv, r), st.prev_resv),
        prev_prop=hset(_fold_prev(st.prev_prop, p), st.prev_prop),
        prev_limit=hset(_fold_prev(st.prev_limit, l), st.prev_limit),
        prev_arrival=hset(t0, st.prev_arrival),
        q_arrival=q_arrival,
        q_cost=q_cost,
        depth=(st.depth + counts.astype(jnp.int32)),
        cur_rho=hset(rho, st.cur_rho, requesting),
        cur_delta=hset(delta, st.cur_delta, requesting),
    )


# ----------------------------------------------------------------------
# small host-facing helpers
# ----------------------------------------------------------------------

def mark_idle(state: EngineState, slots: jnp.ndarray) -> EngineState:
    """GC support: mark the given slots idle (oracle do_clean's idle
    branch; reference :1206-1255)."""
    return state._replace(idle=state.idle.at[slots].set(True))


def deactivate(state: EngineState, slots: jnp.ndarray) -> EngineState:
    """GC support: erase clients (slots are recycled by the host)."""
    return state._replace(
        active=state.active.at[slots].set(False),
        depth=state.depth.at[slots].set(0),
    )
