"""Prefix-commit speculative serving: thousands of decisions per O(N) pass.

The exact engine (`kernels.engine_step`) pays an O(N) masked-argmin per
decision -- semantically perfect, bandwidth-bound at scale.  This module
exploits a structural fact about dmClock's decision rule: at a fixed
``now`` the serial engine always serves the MINIMUM of one unified
lexicographic key space over clients,

    class 0  reservation-eligible  (head_resv <= now)     key = resv tag
    class 1  ready weight          (effective-ready,      key = prop tag
                                    prop < MAX)                 + delta
    class 2  limit-break           (AtLimit::Allow only)  key = prop tag
                                                                + delta

because the constraint phase takes absolute priority over the weight
phase (reference do_next_request :1124-1151), and the Allow fallback
only fires when both are empty (:1157-1165).  A full sort of the
per-client (class, key, creation-order) triples therefore yields the
ENTIRE candidate service order -- across regime boundaries -- in one
pass, and the engine commits the longest prefix of it that is provably
what the serial engine would have served, computed ON DEVICE.

Exactness argument (differentially tested against `engine_run`):
candidates are served in sorted (class, key, order) ascending order.
Serving candidate p re-enters its client at its EXIT key x_p -- the
unified key of its freshly-tagged next head (+inf if it empties or
leaves the candidate set).  The speculative order equals the serial
order up to position q iff ``min_{p<q} x_p > (class_q, key_q, order_q)``
at every position <= q -- the serial engine would have picked the
re-entered head first otherwise.  Since entry keys ascend and the
cumulative min only descends, the condition fails monotonically: the
first failing position ends the exact prefix.  Guaranteed progress:
whenever the serial engine would RETURN a request at ``now``, the
prefix is >= 1.

**Serve chains** (``chain_depth`` > 1) are what make interleaved-regime
workloads batch: a weight serve's reservation-debt reduction (reference
reduce_reservation_tags :1077-1111) often drags the served client's
next reservation tag back under ``now``.  At that serial moment the
client is the ONLY class-0 candidate (a weight serve happens only when
no reservation tag was eligible, and no other client's state changed),
so the serial engine provably serves THAT client's reservation
requests next, until its tag climbs past ``now`` again.  The chain
pre-computes this whole run -- one weight serve plus its induced
constraint serves, up to ``chain_depth`` total -- as ONE sort unit
whose exit key is back in weight space, so per-decision phase flips
(the reference's balanced mixed-QoS steady state) no longer cut the
committed prefix.  A chain that would exceed ``chain_depth`` exits at
its exact class-0 key, which stops the prefix right after the unit --
conservative, never inexact.

AtLimit::Allow (``allow_limit_break``) adds class 2: clients past their
limit, served lowest-proportion-first when classes 0/1 are empty, with
``limit_break`` flagged.  Restriction (checked by the caller): every
active client has weight > 0.  With a weight-0 (prop == MAX_TAG)
client that is ready, the reference's Allow fallback switches to
reservation order globally (the ready-heap top pins at MAX,
:1157-1165), which per-client classification cannot express.

Restrictions (checked by the caller): monotonic `now`, fixed `now`
within a batch.  The stored `ready` flags are superseded by the
computed `limit <= now` (equivalent under monotonic now, since a
promotion that serial processing would perform later in the batch is
performed here eagerly and verified sound).
"""

from __future__ import annotations

from typing import NamedTuple

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from ..core.timebase import MAX_TAG, MIN_TAG
from ..obs import device as obsdev
from ..obs import flight as obsflight
from ..obs import histograms as obshist
from ..obs import provenance as obsprov
from ..obs import slo as obsslo
from . import kernels
from . import kernels_pallas
from .kernels import (KEY_INF, NONE, RETURNING, Decision, _make_tag,
                      _fold_prev)
from .state import EngineState, TAG_I64_FIELDS


# Selection = ONE full sort on a packed int64 unified key: 2 class
# bits | 32-bit rebased tag | 28-bit rebased creation order.  A full
# sort yields the ENTIRE cross-regime service order, letting the batch
# size k grow to tens of thousands of decisions per O(N) pass.  Tags
# rebase per CLASS (reservation tags and proportion tags live in
# unrelated value spaces, so each class subtracts its own origin);
# rebase-window overflow (entry spread > ~3.2s above its class origin
# after the _EXIT_BIAS reservation) clamps to _KEY_CLAMP:
# harmless for candidates strictly beyond the selection boundary
# (never selectable), and the in-window check fails speculation
# otherwise, so exactness is never at risk (the serial engine takes
# the batch).  The creation-order spread guard is 2^28 live creations.
_KEY_CLAMP = (1 << 32) - 2   # in-window ceiling for real entry keys
_KEY_HI = (1 << 32) - 1      # above-window exit-key clamp (exact for
#                              every in-window boundary: see epk notes)
_EXIT_BIAS = jnp.int64(1) << 30   # window low end reserved for exits
#                                   below their class origin (~1.07s)
_ORDER_LIMIT = jnp.int64(1) << 28
_O_MASK = (jnp.int64(1) << 28) - 1

CLS_RESV = 0      # reservation-eligible: constraint phase
CLS_WEIGHT = 1    # effective-ready: weight phase
CLS_LB = 2        # AtLimit::Allow limit-break: weight phase + flag
CLS_NONE = 3      # non-candidate sentinel (sorts after every class)


def _ready_now(state: EngineState, now):
    """Effective readiness under monotonic now: stored flag OR limit
    passed (the promote loop marks exactly {limit <= now},
    reference :1135-1144)."""
    return state.head_ready | (state.head_limit <= now)


def on_tpu() -> bool:
    """Whether the program being traced lands on a TPU: the platform
    of the ``jax.default_device`` scope when one is set, else the
    default backend's.  Picks the Pallas kernels at trace time."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() == "tpu"
    return (dev if isinstance(dev, str) else dev.platform) == "tpu"


class RingWindow(NamedTuple):
    """Per-epoch prefetch of the tail rings.

    A speculative batch pops at most ``chain_depth`` requests per
    client, so a window of [w, N] ring positions ``q_head0 ..
    q_head0+w-1`` covers it.  Prefetching replaces the per-batch ring
    gather, which XLA lowers to a dense read of the ENTIRE [N, Q] ring
    pair (~200 MB/batch at bench shapes -- measured as 60x the
    window's traffic)."""

    arr: jnp.ndarray    # int64[w, N] arrivals at q_head0 + j
    cost: jnp.ndarray   # int64[w, N]
    q0: jnp.ndarray     # int32[N] q_head at prefetch time


# Pallas row-rotate: the barrel shift runs in VMEM (one HBM read +
# write per chunk) instead of log2(Q) full HBM passes.  The kernel is
# gridless and the host slices VMEM-sized row chunks (gridding it is
# open perf work); int64 rings are bitcast to int32 lane pairs (a row
# rotation by 2*q0 on the pair plane is the int64 rotation by q0).
# The chunk scales inversely with ring width to stay inside the 16MB
# scoped-VMEM budget (2048 rows at Q=128 = 256 lanes; compiled for
# v5e at N=100k in tests/test_tpu_compile.py).
_ROT_LANE_BUDGET = 2048 * 256


def _rot_chunk(q: int) -> int:
    return max(8, (_ROT_LANE_BUDGET // (2 * q)) // 8 * 8)


def _rotate_kernel(q_ref, x_ref, o_ref, *, q: int):
    x = x_ref[...]                       # [chunk, 2Q] int32
    shifts = q_ref[...]                  # [chunk, 2Q] int32, in [0, Q)
    one = jnp.int32(1)
    s = 0
    while (1 << s) < q:
        bit2 = ((shifts >> jnp.int32(s)) & one) == one
        d = jnp.int32((2 * q - 2 * (1 << s)) % (2 * q))
        x = jnp.where(bit2, pltpu.roll(x, shift=d, axis=1), x)
        s += 1
    o_ref[...] = x


def _rotate_rows_pallas(ring, q0, wsize: int, *, q0t=None,
                        interpret: bool = False):
    """out[w, i] = ring[i, (q0[i]+w) % Q] for w < wsize (int64 ring).
    ``q0t`` lets callers share the lane-tiled shift plane between the
    arrival and cost rotations."""
    from jax.experimental import pallas as pl

    n, q = ring.shape
    chunk = _rot_chunk(q)
    i32 = lax.bitcast_convert_type(ring, jnp.int32).reshape(n, 2 * q)
    pad = (-n) % chunk
    if pad:
        i32 = jnp.pad(i32, ((0, pad), (0, 0)))
    if q0t is None:
        q0t = _tile_shifts(q0, q, n + pad)
    call = pl.pallas_call(
        functools.partial(_rotate_kernel, q=q),
        out_shape=jax.ShapeDtypeStruct((chunk, 2 * q), jnp.int32),
        interpret=interpret)
    # slice each chunk to the window BEFORE concatenating: the full
    # rotated ring is never materialized in HBM
    outs = [call(q0t[c:c + chunk], i32[c:c + chunk])
            [:, :2 * wsize]
            for c in range(0, n + pad, chunk)]
    rot = jnp.concatenate(outs, axis=0)
    win = rot[:n].reshape(n, wsize, 2)
    return lax.bitcast_convert_type(win, jnp.int64).T


def _tile_shifts(q0, q: int, n_padded: int):
    q0 = jnp.pad(q0, (0, n_padded - q0.shape[0]))
    return jnp.broadcast_to(q0[:, None],
                            (n_padded, 2 * q)).astype(jnp.int32)


def _rotate_rows_xla(ring, q0, wsize: int):
    q = ring.shape[1]
    r = ring
    s = 0
    while (1 << s) < q:
        bit = ((q0 >> s) & 1).astype(bool)
        r = jnp.where(bit[:, None], jnp.roll(r, -(1 << s), axis=1), r)
        s += 1
    return r[:, :wsize].T


def ring_window(state: EngineState, m: int,
                use_pallas: bool | None = None) -> RingWindow:
    """Prefetch the next ``min(m, Q)`` ring elements of every client,
    transposed to [w, N] for cheap per-batch row selects.

    Built by barrel-shifting each client's ring left by its own
    ``q_head``: a Pallas VMEM kernel on TPU, log2(Q) masked dense XLA
    rolls elsewhere (TPU gathers with per-row indices serialize --
    measured 10x the rolls' cost for a 32-wide window; a vmapped
    dynamic-slice was 50x).  Window rows past a client's queued tail
    carry stale ring values -- reads of them only happen after the
    client drained, and are masked at commit.

    ``use_pallas`` overrides the backend auto-pick: callers that wrap
    this in ``vmap`` must pass False -- batching would add a grid
    dimension to the gridless kernel."""
    q = state.ring_capacity
    q0 = state.q_head
    wsize = min(m, q)

    # the Pallas path needs a full lane tile (2q >= 128 int32 lanes)
    if use_pallas is None:
        use_pallas = on_tpu() and q >= 64
    if use_pallas:
        n = q0.shape[0]
        q0t = _tile_shifts(q0, q, n + ((-n) % _rot_chunk(q)))
        rot = functools.partial(_rotate_rows_pallas, q0=q0,
                                wsize=wsize, q0t=q0t)
    else:
        rot = functools.partial(_rotate_rows_xla, q0=q0, wsize=wsize)
    return RingWindow(arr=rot(state.q_arrival), cost=rot(state.q_cost),
                      q0=q0)


def _window_rows(state: EngineState, window: RingWindow, depth: int):
    """Rows ``off .. off+depth-1`` of the prefetched window for every
    client, where ``off = q_head - q0`` is how many rows the client
    consumed since the prefetch.  Unrolled one-hot selects -- a [w, N]
    take_along_axis lowers to a serializing gather (measured 20x
    slower)."""
    wsize = window.arr.shape[0]
    off = jnp.remainder(state.q_head - window.q0,
                        state.ring_capacity).astype(jnp.int32)
    arr_rows, cost_rows = [], []
    for d in range(depth):
        narr = window.arr[min(d, wsize - 1)]
        ncost = window.cost[min(d, wsize - 1)]
        for j in range(d + 1, wsize):
            pick = off == j - d
            narr = jnp.where(pick, window.arr[j], narr)
            ncost = jnp.where(pick, window.cost[j], ncost)
        arr_rows.append(narr)
        cost_rows.append(ncost)
    return arr_rows, cost_rows


def _window_heads(state: EngineState, window: RingWindow):
    """Every client's next tail element (new head after a pop)."""
    arr_rows, cost_rows = _window_rows(state, window, 1)
    return arr_rows[0], cost_rows[0]


def _heads_rows(heads, depth: int):
    """Normalize a ``heads`` argument to per-step row lists.

    Accepts the single-pop pair (narr[N], ncost[N]) for depth 1, or
    stacked [w, N] arrays (a ``ring_window``'s arr/cost with w >=
    depth) for chained pops."""
    arr, cost = heads
    if arr.ndim == 1:
        assert depth == 1
        return [arr], [cost]
    assert arr.shape[0] >= depth, \
        f"heads window {arr.shape[0]} rows < chain depth {depth}"
    return [arr[j] for j in range(depth)], [cost[j] for j in range(depth)]


# ----------------------------------------------------------------------
# unified candidate classification
# ----------------------------------------------------------------------

def _unified_class(now, has, resv, ready, prop, eff, allow: bool):
    """(class, key) in the unified candidate order the serial engine
    serves (reference do_next_request :1115-1186): constraint phase
    first (class 0, by reservation tag), then ready weight (class 1,
    by effective proportion), then -- Allow only -- limit-break
    (class 2, by effective proportion; :1157-1165, reachable because
    the caller guarantees weight > 0 for every active client, see
    module docstring).  Non-candidates get (CLS_NONE, KEY_INF).

    ONE definition shared by entry classification and the chain's
    exit classification -- they differ only in the readiness
    predicate (stored-flag-or-limit for current heads, limit-only for
    freshly popped ones)."""
    prop_ok = prop < MAX_TAG
    c0 = has & (resv <= now)
    c1 = has & ~c0 & ready & prop_ok
    cls = jnp.where(c0, CLS_RESV,
                    jnp.where(c1, CLS_WEIGHT, CLS_NONE))
    key = jnp.where(c0, resv, jnp.where(c1, eff, KEY_INF))
    if allow:
        c2 = has & ~c0 & ~c1 & prop_ok
        cls = jnp.where(c2, CLS_LB, cls)
        key = jnp.where(c2, eff, key)
    return cls.astype(jnp.int32), key


def _classify(state: EngineState, now, allow: bool):
    """Entry (class, key) per client (see ``_unified_class``)."""
    has_req = state.active & (state.depth > 0)
    return _unified_class(
        now, has_req, state.head_resv, _ready_now(state, now),
        state.head_prop, state.head_prop + state.prop_delta, allow)


# ----------------------------------------------------------------------
# dense serve chains
# ----------------------------------------------------------------------

class ChainServe(NamedTuple):
    """Elementwise ([N]) serve-chain computation: what every client's
    state would become after serving its full chain this batch.
    Scatter-free -- TPU scatters serialize badly, so the chain is
    computed densely for every client and committed with ``jnp.where``
    selects.  Rows outside the committed set are garbage and masked at
    commit."""

    depth: jnp.ndarray        # int32[N] after the chain
    qadv: jnp.ndarray         # int32[N] ring pops performed
    length: jnp.ndarray       # int32[N] serves in the chain (>=1 cand)
    head_resv: jnp.ndarray    # int64[N] final head tag
    head_prop: jnp.ndarray
    head_limit: jnp.ndarray
    head_arrival: jnp.ndarray
    head_cost: jnp.ndarray
    head_rho: jnp.ndarray
    prev_resv: jnp.ndarray
    prev_prop: jnp.ndarray
    prev_limit: jnp.ndarray
    prev_arrival: jnp.ndarray
    exit_cls: jnp.ndarray     # int32[N] unified class after the chain
    exit_key: jnp.ndarray     # int64[N] unified key after the chain
    cost_acc: jnp.ndarray     # int64[N] summed cost of the chain's
    #                           serves (garbage outside the committed
    #                           set, masked at commit like every other
    #                           dense chain field)


def _chain_serve(state: EngineState, now, arr_rows, cost_rows,
                 cls, allow: bool,
                 anticipation_ns: int) -> ChainServe:
    """The vectorized pop+retag (pop_process_request / update_next_tag /
    reduce_reservation_tags, reference :1021-1111) iterated
    ``len(arr_rows)`` times for EVERY client.

    Step 0 serves the entry head in the entry class's phase (weight
    phase pays the reservation debt, :1077-1111).  Steps >= 1 are the
    INDUCED constraint serves: they run only for weight/limit-break
    entries whose just-retagged reservation tag fell to ``now`` or
    below -- at that serial moment the client is the only class-0
    candidate, so the serial engine provably serves it next.  The
    chain stops when the tag climbs past ``now``, the queue drains, or
    the depth cap is hit; the exit (class, key) is the client's exact
    re-entry position in the unified order (KEY_INF when it leaves)."""
    depth_cap = len(arr_rows)
    is_cand = cls != CLS_NONE
    chains = (cls == CLS_WEIGHT) | (cls == CLS_LB)
    phase1 = chains                       # weight-phase entry serve

    h_resv, h_prop, h_limit = (state.head_resv, state.head_prop,
                               state.head_limit)
    h_arr, h_cost, h_rho = (state.head_arrival, state.head_cost,
                            state.head_rho)
    p_resv, p_prop, p_limit, p_arr = (state.prev_resv, state.prev_prop,
                                      state.prev_limit,
                                      state.prev_arrival)
    depth = state.depth
    qadv = jnp.zeros_like(state.q_head)
    length = jnp.zeros_like(state.q_head)
    cost_acc = jnp.zeros_like(h_resv)
    cont = is_cand

    for j in range(depth_cap):
        narr, ncost = arr_rows[j], cost_rows[j]
        nr, np_, nl = _make_tag(
            h_resv, h_prop, h_limit, h_arr,
            state.resv_inv, state.weight_inv, state.limit_inv,
            state.cur_delta, state.cur_rho, narr, ncost,
            anticipation_ns)
        if j == 0:
            off = jnp.where(phase1,
                            state.resv_inv * (h_cost + h_rho),
                            jnp.zeros_like(h_resv))
        else:
            off = jnp.zeros_like(h_resv)

        new_depth = depth - 1
        has_more = new_depth > 0
        upd = cont
        updh = cont & has_more
        # delivered-cost accumulation (the SLO window block's cost
        # column): the head served at this step is the CURRENT h_cost
        cost_acc = cost_acc + jnp.where(upd, h_cost, jnp.int64(0))

        new_h_resv = nr - off
        pr = jnp.where(has_more, _fold_prev(p_resv, nr), p_resv) - off
        pp = jnp.where(has_more, _fold_prev(p_prop, np_), p_prop)
        pl_ = jnp.where(has_more, _fold_prev(p_limit, nl), p_limit)

        h_resv = jnp.where(updh, new_h_resv, h_resv)
        h_prop = jnp.where(updh, np_, h_prop)
        h_limit = jnp.where(updh, nl, h_limit)
        h_arr = jnp.where(updh, narr, h_arr)
        h_cost = jnp.where(updh, ncost, h_cost)
        h_rho = jnp.where(updh, state.cur_rho, h_rho)
        p_resv = jnp.where(upd, pr, p_resv)
        p_prop = jnp.where(upd, pp, p_prop)
        p_limit = jnp.where(upd, pl_, p_limit)
        p_arr = jnp.where(updh, narr, p_arr)
        depth = jnp.where(upd, new_depth, depth).astype(jnp.int32)
        qadv = (qadv + updh).astype(jnp.int32)
        length = (length + upd).astype(jnp.int32)

        # continue only for weight/lb entries whose fresh reservation
        # tag is eligible: the induced-constraint-serve condition
        cont = cont & chains & has_more & (new_h_resv <= now)

    # exit classification on the final head (shared definition,
    # ``_unified_class``; a freshly popped head's stored ready flag is
    # False, so effective readiness is exactly limit <= now).  A chain
    # that hit the depth cap while still class-0-eligible exits at its
    # exact (0, resv) key: class 0 sorts before every remaining
    # class-1/2 entry, so the prefix stops right after the unit --
    # conservative (the serial engine would keep serving this client),
    # never inexact.
    has = state.active & (depth > 0)
    exit_cls, exit_key = _unified_class(
        now, has, h_resv, h_limit <= now, h_prop,
        h_prop + state.prop_delta, allow)

    return ChainServe(
        depth=depth, qadv=qadv, length=length,
        head_resv=h_resv, head_prop=h_prop, head_limit=h_limit,
        head_arrival=h_arr, head_cost=h_cost, head_rho=h_rho,
        prev_resv=p_resv, prev_prop=p_prop, prev_limit=p_limit,
        prev_arrival=p_arr,
        exit_cls=exit_cls.astype(jnp.int32), exit_key=exit_key,
        cost_acc=cost_acc)


def _commit_chains(state: EngineState, sel,
                   chain: ChainServe) -> EngineState:
    """Apply the dense chain result to the rows in ``sel``: pure
    elementwise selects, no scatters."""

    def pick(pred, new, old):
        return jnp.where(pred, new, old)

    popped = sel & (chain.qadv > 0)
    return state._replace(
        depth=pick(sel, chain.depth, state.depth),
        q_head=pick(popped,
                    (state.q_head + chain.qadv) % state.ring_capacity,
                    state.q_head).astype(jnp.int32),
        head_resv=pick(popped, chain.head_resv, state.head_resv),
        head_prop=pick(popped, chain.head_prop, state.head_prop),
        head_limit=pick(popped, chain.head_limit, state.head_limit),
        head_arrival=pick(popped, chain.head_arrival,
                          state.head_arrival),
        head_cost=pick(popped, chain.head_cost, state.head_cost),
        head_rho=pick(popped, chain.head_rho, state.head_rho),
        head_ready=state.head_ready & ~sel,
        prev_resv=pick(sel, chain.prev_resv, state.prev_resv),
        prev_prop=pick(sel, chain.prev_prop, state.prev_prop),
        prev_limit=pick(sel, chain.prev_limit, state.prev_limit),
        prev_arrival=pick(popped, chain.prev_arrival,
                          state.prev_arrival),
    )


# ----------------------------------------------------------------------
# unified prefix selection
# ----------------------------------------------------------------------

def _pack(cls, krel, o):
    """Lexicographic (class, key, order) as one int64: 2 class bits |
    32 key bits | 28 order bits.  ``o`` is masked against garbage
    orders on sentinel rows; all inputs int64."""
    return ((cls.astype(jnp.int64) << 60) | (krel << 28)
            | (o & _O_MASK))


# ----------------------------------------------------------------------
# selection backends: full sort vs histogram (radix) k-selection
# ----------------------------------------------------------------------
#
# The sort backend (the original engine) pays one O(N log N) lax.sort
# over 4-5 arrays to order ALL clients, then commits the first <= k.
# But selection only needs the k-th boundary plus membership; the
# ORDER is needed only for the k-sized decision emit.  The radix
# backend exploits that: a multi-pass dense histogram finds the exact
# k-th smallest packed key (no sorts, no gathers -- findings 4/8/10),
# dense elementwise ops compute membership, a prefix-sum compaction
# writes the <= k members into [k] arrays, and the expensive sort runs
# only over those k entries (honoring finding 8: cost/order/exit-key
# ride the small sort as payloads, never gathered).  Packed keys are
# unique among candidates (creation order breaks ties), so the small
# sort reproduces the big sort's first k positions BIT-EXACTLY; the
# only divergence is in masked padding lanes no caller reads
# (pinned by tests/test_radix.py).
#
# Digit width: dense one-hot histograms cost passes * 2^bits * N
# comparisons = (64/b) * 2^b * N, minimized at small b; 4-bit digits
# (16 passes of 16-bucket histograms) cost 8x less than 8-bit ones
# and keep every pass a pure vectorized compare+reduce.
#
# The histogram walk itself lives in ``kernels`` now (radix_kth_key):
# the calendar engine's bucketed stop-key ladder reuses it, so the
# machinery is shared instead of prefix-path-private.

_radix_kth_key = kernels.radix_kth_key


def _select_radix(pk_dense, iota, epk, cost32, lens, k: int, kk: int):
    """Histogram k-selection + small sort: the sorted first-kk columns
    of the big sort, built without ordering the other N-kk entries.

    Returns (pks, idxs, rpk, costs, lens_s) shaped [k], with sentinel
    padding (KEY_INF / -1 / KEY_INF / 0 / 0) past the member count --
    identical to the sort backend at every position a caller reads
    (every lane past the committed count is masked downstream).
    ``lens`` may be None (flat batches)."""
    t_kth = _radix_kth_key(pk_dense, kk)
    # membership: at most kk candidates (packed keys are unique among
    # candidates, so count == kk exactly when enough exist); the
    # KEY_INF exclusion drops sentinel rows when kk > live count
    member = (pk_dense <= t_kth) & (pk_dense < jnp.int64(KEY_INF))
    dest = jnp.cumsum(member.astype(jnp.int32)) - 1
    dest = jnp.where(member, dest, jnp.int32(k))   # k = dropped

    def compact(src, fill):
        out = jnp.full((k,), fill, dtype=src.dtype)
        return out.at[dest].set(src, mode="drop")

    ops = [compact(pk_dense, jnp.int64(KEY_INF)),
           compact(iota, jnp.int32(-1)),
           compact(epk, jnp.int64(KEY_INF)),
           compact(cost32, jnp.int32(0))]
    if lens is not None:
        ops.append(compact(lens, jnp.int32(0)))
        return lax.sort(tuple(ops), num_keys=1)
    pks, idxs, rpk, costs = lax.sort(tuple(ops), num_keys=1)
    return pks, idxs, rpk, costs, jnp.ones((k,), dtype=jnp.int32)


class _Selection(NamedTuple):
    """Everything a caller needs to commit + emit a unified prefix."""

    idxs: jnp.ndarray        # int32[k] sorted candidate slots
    cls_s: jnp.ndarray       # int32[k] sorted entry classes
    cost_s: jnp.ndarray      # int32[k] sorted entry (head) costs
    len_s: jnp.ndarray       # int32[k] sorted chain lengths
    count_units: jnp.ndarray  # int32 committed sort units
    count: jnp.ndarray       # int32 committed DECISIONS (sum of len)
    guards_ok: jnp.ndarray   # bool
    state: EngineState       # after the committed prefix
    last_client: jnp.ndarray  # int32 slot of the final committed unit
    cost_pc: jnp.ndarray     # int64[N] delivered cost per client over
    #                          the committed prefix (0 off-prefix)
    margin_s: jnp.ndarray    # int64[k] winner margin over the exact
    #                          runner-up per committed unit, ns
    #                          (-1 = no runner-up; obs.provenance)


def _unified_prefix(state: EngineState, now, k: int, *,
                    chain_depth: int, anticipation_ns: int,
                    allow: bool, heads, max_count,
                    select_impl: str = "sort") -> _Selection:
    """Classify, chain, select (full sort or histogram k-selection,
    ``select_impl``), and commit the longest exact prefix."""
    assert select_impl in ("sort", "radix"), select_impl
    if heads is None:
        heads = ring_window(state, chain_depth)
        heads = (heads.arr, heads.cost)
    arr_rows, cost_rows = _heads_rows(heads, chain_depth)

    cls, key = _classify(state, now, allow)
    chain = _chain_serve(state, now, arr_rows, cost_rows, cls, allow,
                         anticipation_ns)

    is_cand = cls != CLS_NONE
    # --- packed rebase over two key spaces: reservation tags
    # (class 0) and effective proportion tags (classes 1/2).
    # PER-CLASS rebase origins: each class's minimum entry rebases to
    # the bias, so position 0 of the sort is always in-window and a
    # nonempty candidate set always commits >= 1 unit (guaranteed
    # progress), whatever the spread between the classes' key spaces.
    # _EXIT_BIAS reserves the window's low end for exits that land
    # BELOW their class origin (e.g. a constraint serve re-entering
    # weight space under the ready minimum): within the bias they
    # rebase exactly; further below they clamp to 0, which only
    # shortens the prefix -- conservative, never inexact.
    def class_min(m):
        return jnp.min(jnp.where(m, key, KEY_INF))

    kresv = class_min(cls == CLS_RESV)
    kprop1 = class_min(cls == CLS_WEIGHT)
    kprop2 = class_min(cls == CLS_LB)

    def origin_of(c):
        return jnp.where(c == CLS_RESV, kresv,
                         jnp.where(c == CLS_WEIGHT, kprop1, kprop2))

    krel = jnp.clip(key - origin_of(cls) + _EXIT_BIAS, 0,
                    jnp.int64(_KEY_CLAMP))

    # order rebased like the keys: creation indices grow without bound,
    # so the 28-bit pack must be of the spread, not the absolute value
    omin = jnp.min(jnp.where(is_cand, state.order, jnp.int64(1) << 62))
    o64 = state.order - omin
    omax = jnp.max(jnp.where(is_cand, state.order, omin))
    # the cost guard masks to real candidates: an oversized cost on an
    # inactive/non-candidate row must not disable the fastpath forever
    cost_ok = jnp.max(jnp.where(is_cand, state.head_cost, 0)) \
        < (jnp.int64(1) << 31)
    guards_ok = (omax - omin < _ORDER_LIMIT) & cost_ok

    pk_dense = jnp.where(is_cand, _pack(cls, krel, o64),
                         jnp.int64(KEY_INF))

    # exit keys in the same packed space.  Clamping an exit low (past
    # the bias below its class origin) only shortens the prefix --
    # conservative, never inexact; clamping high (_KEY_HI, above the
    # entry clamp) preserves ``exit > boundary`` for every committable
    # boundary, which is strictly in-window.
    ekrel = jnp.clip(chain.exit_key - origin_of(chain.exit_cls)
                     + _EXIT_BIAS, 0, jnp.int64(_KEY_HI))
    epk = jnp.where(chain.exit_cls == CLS_NONE, jnp.int64(KEY_INF),
                    _pack(chain.exit_cls, ekrel, o64))

    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    kk = min(k, key.shape[0])

    def trim(a, fill):
        a = a[:kk]
        if kk < k:      # k beyond the population: sentinel padding
            a = jnp.concatenate(
                [a, jnp.full((k - kk,), fill, dtype=a.dtype)])
        return a

    if select_impl == "radix":
        pks, idxs, rpk, costs, lens = _select_radix(
            pk_dense, iota, epk, state.head_cost.astype(jnp.int32),
            chain.length if chain_depth > 1 else None, k, kk)
    elif chain_depth == 1:
        pks, idxs, rpk, costs = lax.sort(
            (pk_dense, iota, epk,
             state.head_cost.astype(jnp.int32)), num_keys=1)
        lens = jnp.ones((k,), dtype=jnp.int32)
        pks, idxs = trim(pks, KEY_INF), trim(idxs, -1)
        rpk, costs = trim(rpk, KEY_INF), trim(costs, 0)
    else:
        pks, idxs, rpk, costs, lens = lax.sort(
            (pk_dense, iota, epk,
             state.head_cost.astype(jnp.int32), chain.length),
            num_keys=1)
        lens = trim(lens, 0)
        pks, idxs = trim(pks, KEY_INF), trim(idxs, -1)
        rpk, costs = trim(rpk, KEY_INF), trim(costs, 0)

    # exclusive cumulative min of exit keys over the sorted order
    cm = lax.associative_scan(jnp.minimum, rpk)
    cm_excl = jnp.concatenate(
        [jnp.full((1,), jnp.int64(KEY_INF), dtype=jnp.int64), cm[:-1]])

    in_window = ((pks >> 60) < CLS_NONE) & \
        (((pks >> 28) & _KEY_HI) < _KEY_CLAMP)
    ok_q = in_window & (cm_excl > pks)
    count_units = jnp.where(jnp.all(ok_q), jnp.int32(k),
                            jnp.argmax(~ok_q).astype(jnp.int32))
    count_units = jnp.where(guards_ok, count_units, jnp.int32(0))
    if max_count is not None:
        assert chain_depth == 1, \
            "max_count caps decisions; only supported at chain_depth=1"
        count_units = jnp.minimum(count_units, jnp.int32(max_count))

    j = jnp.arange(k, dtype=jnp.int32)
    served = j < count_units
    cls_s = (pks >> 60).astype(jnp.int32)   # >= CLS_NONE on sentinels

    # provenance margins (obs.provenance): at the instant unit j
    # commits, the candidate set is {entries j+1..} plus the re-entry
    # exit keys of the already-served prefix p < j -- so the EXACT
    # runner-up is min(pks[j+1], cm_excl[j]), both already
    # materialized.  The >> 28 strips the order bits (packed key =
    # cls<<60 | rebased-ns<<28 | order): a same-class margin is the
    # tag distance in ns; a cross-class one carries the class step
    # (>= 2^32 ns -- "the phase ladder, not the tag, decided").  -1 =
    # no runner-up existed (sole candidate).  Dead code unless a
    # provenance/flight consumer reads it (XLA DCE).
    nxt = jnp.concatenate(
        [pks[1:], jnp.full((1,), jnp.int64(KEY_INF))])
    runner = jnp.minimum(nxt, cm_excl)
    margin_s = jnp.where(served & (runner < jnp.int64(KEY_INF)),
                         (runner - pks) >> 28, jnp.int64(-1))
    if chain_depth == 1:
        count = count_units
    else:
        count = jnp.sum(jnp.where(served, lens, 0)).astype(jnp.int32)

    # commit: dense membership is ``packed(key) <= packed boundary``
    # (packed keys are unique).  The boundary pk[count-1] is read as a
    # masked max over the sorted prefix, not a dynamic gather --
    # scalar gathers from vectors serialize on this stack (PROFILE.md
    # findings 4/8).
    boundary = jnp.max(jnp.where(served, pks, jnp.int64(-1)))
    sel = pk_dense <= boundary
    new_state = _commit_chains(state, sel, chain)

    # stored-flag parity (promote loop, reference :1135-1144): every
    # weight-phase (class >= 1 entry) decision promotes current heads
    # with limit <= now.  Classes sort ascending, so the LAST committed
    # unit has the batch's max class: if it is >= 1, its entry decision
    # ran the batch's final promote pass, and the only head that pass
    # never saw is the one its own chain popped into place.  With no
    # class >= 1 unit committed no promote pass ran, so the flags stay
    # untouched (pops still clear them via _commit_chains).
    sel_last = j == count_units - 1
    cls_last = jnp.max(jnp.where(sel_last, cls_s, -1))
    last_client = jnp.max(jnp.where(sel_last, idxs, -1))
    do_promote = (count_units > 0) & (cls_last >= CLS_WEIGHT)
    has_req_after = new_state.active & (new_state.depth > 0)
    promoted = new_state.head_ready | \
        (has_req_after & (new_state.head_limit <= now))
    promoted = promoted & (
        jnp.arange(state.capacity, dtype=jnp.int32) != last_client)
    new_state = new_state._replace(head_ready=jnp.where(
        do_promote, promoted, new_state.head_ready))

    return _Selection(idxs=idxs, cls_s=cls_s, cost_s=costs, len_s=lens,
                      count_units=count_units, count=count,
                      guards_ok=guards_ok, state=new_state,
                      last_client=last_client,
                      cost_pc=jnp.where(sel, chain.cost_acc,
                                        jnp.int64(0)),
                      margin_s=margin_s)


# ----------------------------------------------------------------------
# flat (chain_depth=1) batches: one decision per sort unit
# ----------------------------------------------------------------------

class PrefixBatch(NamedTuple):
    """Result of one prefix-commit attempt."""

    state: EngineState
    count: jnp.ndarray     # int32: decisions committed (exact serial
    #                        prefix; 0 = nothing eligible at `now`)
    guards_ok: jnp.ndarray  # bool: rebase-window guards held; when
    #                         False count is 0 and the caller must use
    #                         the serial engine for this batch
    decisions: Decision    # [k]; slots -1 / type NONE past `count`
    cost_pc: object = None  # int64[N] delivered cost per client (the
    #                         SLO window block's cost column feed)
    margins: object = None  # int64[k] per-decision winner margin, ns
    #                         (-1 = no runner-up; obs.provenance)


def speculate_prefix_batch(state: EngineState, now, k: int, *,
                           anticipation_ns: int,
                           heads=None,
                           max_count=None,
                           allow_limit_break: bool = False,
                           select_impl: str = "sort"
                           ) -> PrefixBatch:
    """One prefix-commit batch over the unified candidate order: the
    longest exact prefix of the sorted (class, key, order) triples
    commits, crossing constraint<->weight regime boundaries inside a
    single batch (reference do_next_request :1115-1186 makes a fresh
    phase choice per decision; the class field encodes it per unit).

    ``max_count`` (optional int32 scalar, may be traced) caps the
    committed prefix: a shorter prefix of an exact prefix is still
    exact, so callers can budget decisions (e.g. a simulator serving
    at most its remaining slice capacity) without losing parity."""
    s = _unified_prefix(state, now, k, chain_depth=1,
                        anticipation_ns=anticipation_ns,
                        allow=allow_limit_break, heads=heads,
                        max_count=max_count, select_impl=select_impl)
    j = jnp.arange(k, dtype=jnp.int32)
    served = j < s.count_units
    phase = jnp.where(s.cls_s >= CLS_WEIGHT, 1, 0).astype(jnp.int32)
    decisions = Decision(
        type=jnp.where(served, RETURNING, NONE).astype(jnp.int32),
        slot=jnp.where(served, s.idxs, -1).astype(jnp.int32),
        phase=jnp.where(served, phase, 0),
        cost=jnp.where(served, s.cost_s.astype(jnp.int64), 0),
        when=jnp.zeros((k,), dtype=jnp.int64),
        limit_break=served & (s.cls_s >= CLS_LB),
    )
    return PrefixBatch(state=s.state, count=s.count,
                       guards_ok=s.guards_ok, decisions=decisions,
                       cost_pc=s.cost_pc, margins=s.margin_s)


# ----------------------------------------------------------------------
# chained batches: one sort unit = up to chain_depth decisions
# ----------------------------------------------------------------------

class ChainBatch(NamedTuple):
    """Result of one chained prefix-commit attempt: compact unit form.

    The flat decision stream is ``slot[q]`` repeated ``length[q]``
    times for each committed unit q in order, phases = the unit's
    entry phase (class >= 1 -> weight) followed by length-1 constraint
    serves (see ``expand_units``)."""

    state: EngineState
    count: jnp.ndarray       # int32 committed DECISIONS
    unit_count: jnp.ndarray  # int32 committed sort units
    guards_ok: jnp.ndarray
    slot: jnp.ndarray        # int32[k] unit client (-1 pad)
    cls: jnp.ndarray         # int32[k] unit entry class
    length: jnp.ndarray      # int32[k] unit decisions
    cost_pc: object = None   # int64[N] delivered cost per client
    margins: object = None   # int64[k] per-unit winner margin, ns


def speculate_chain_batch(state: EngineState, now, k: int, *,
                          chain_depth: int, anticipation_ns: int,
                          heads=None,
                          allow_limit_break: bool = False,
                          select_impl: str = "sort"
                          ) -> ChainBatch:
    """One prefix-commit batch with serve chains (see module
    docstring): each sort unit serves a client up to ``chain_depth``
    times -- a weight serve plus the constraint serves its
    reservation-debt reduction induces -- so interleaved-regime
    streams commit in long prefixes."""
    s = _unified_prefix(state, now, k, chain_depth=chain_depth,
                        anticipation_ns=anticipation_ns,
                        allow=allow_limit_break, heads=heads,
                        max_count=None, select_impl=select_impl)
    j = jnp.arange(k, dtype=jnp.int32)
    served = j < s.count_units
    return ChainBatch(
        state=s.state, count=s.count, unit_count=s.count_units,
        guards_ok=s.guards_ok,
        slot=jnp.where(served, s.idxs, -1).astype(jnp.int32),
        cls=jnp.where(served, s.cls_s, CLS_NONE).astype(jnp.int32),
        length=jnp.where(served, s.len_s, 0).astype(jnp.int32),
        cost_pc=s.cost_pc, margins=s.margin_s)


def expand_units(slot, cls, length, pre_state, *,
                 limit_break: bool = False):
    """Host-side expansion of committed units into the flat serial
    decision stream (slots, phases, costs, limit_breaks) -- numpy, for
    differential tests and parity harnesses.  ``pre_state`` is the
    EngineState BEFORE the batch (its rings supply the induced serves'
    costs)."""
    import numpy as np

    slot = np.asarray(slot)
    cls = np.asarray(cls)
    length = np.asarray(length)
    head_cost = np.asarray(pre_state.head_cost)
    q_head = np.asarray(pre_state.q_head)
    q_cost = np.asarray(pre_state.q_cost)
    ring = q_cost.shape[1]
    slots, phases, costs, lbs = [], [], [], []
    for u in range(slot.shape[0]):
        c = int(slot[u])
        if c < 0 or length[u] == 0:
            continue
        for step in range(int(length[u])):
            slots.append(c)
            phases.append(1 if (step == 0 and cls[u] >= CLS_WEIGHT)
                          else 0)
            lbs.append(bool(limit_break and step == 0
                            and cls[u] >= CLS_LB))
            if step == 0:
                costs.append(int(head_cost[c]))
            else:
                costs.append(int(q_cost[c, (q_head[c] + step - 1)
                                        % ring]))
    return (np.asarray(slots, np.int32), np.asarray(phases, np.int32),
            np.asarray(costs, np.int64), np.asarray(lbs, bool))


# ----------------------------------------------------------------------
# epoch scans
# ----------------------------------------------------------------------

# state fields the speculative serve path never writes: rings are only
# popped via q_head, and QoS/identity/ingest-time fields are mutated by
# ingest alone, which cannot run mid-epoch.  Keeping them OUT of the
# scan carry stops XLA from shuffling ~100MB of loop-invariant buffers
# per iteration (the rings dominate).
_EPOCH_INVARIANT = ("active", "idle", "order", "resv_inv", "weight_inv",
                    "limit_inv", "prop_delta", "cur_rho", "cur_delta",
                    "q_arrival", "q_cost")
_EPOCH_MUTABLE = tuple(f for f in EngineState._fields
                       if f not in _EPOCH_INVARIANT)


# ----------------------------------------------------------------------
# int32 epoch tag carry (tag_width=32)
#
# The 10 int64 tag/arrival/cost fields in the scan carry
# (state.TAG_I64_FIELDS) are rebased to int32 offsets from per-field
# epoch origins (kernels.rebase32), halving the loop-carried HBM
# traffic of every epoch iteration.  Batches still compute in int64 --
# the widen/narrow converts fuse into the first/last elementwise pass
# of each batch -- so decisions are bit-identical to tag_width=64
# whenever the window holds (pinned by tests/test_radix.py).  A batch
# whose post-state no longer fits the +-2^31 ns window commits NOTHING
# (its carry is kept, its guards_ok output is False, and the
# rebase_fallbacks metric bumps once); the caller reruns the remaining
# batches on the int64 path from the returned state, exactly like the
# sort-key rebase-guard fallback.
# ----------------------------------------------------------------------

class _TagCarry32:
    """The int32 tag carry shared by the three epoch scans: per-field
    origins, entry/per-batch narrowing, widening, and the exit restore
    (one implementation so a fix lands once, not three times).

    Origins are the center of each field's organic (non-sentinel)
    value span at epoch entry, computed over the epoch's LIVE lanes
    only -- clients that are active with work queued.  Centering
    covers entry spreads up to the full 2^32 ns window (~4.3s) with
    symmetric headroom for in-epoch drift (tag climb above,
    weight-debt dips below).  Lanes that cannot serve this epoch
    (inactive or empty at entry; ingest cannot run mid-epoch, so they
    stay that way) are excluded from the window fit and carried as
    zero offsets: every read of their tag fields is masked by
    candidacy (`active & depth > 0`), and the exit restore puts their
    exact entry values back.  Without the live mask, ONE stale idle
    lane whose ancient tag sits outside the window would permanently
    disable the int32 carry on long-running states.

    Epochs whose live entry spread or serve advance exceeds the window
    trip the fit check and fall back exactly (see the section
    comment); low-rate workloads whose tags advance ~1e9 ns per serve
    are expected to live on tag_width=64 (docs/ENGINE.md)."""

    def __init__(self, state: EngineState):
        self.live0 = state.active & (state.depth > 0)

        def organic_center(v):
            fin = self.live0 & (v > MIN_TAG) & (v < MAX_TAG)
            lo = jnp.min(jnp.where(fin, v, MAX_TAG))
            hi = jnp.max(jnp.where(fin, v, MIN_TAG))
            return jnp.where(lo > hi, jnp.int64(0),
                             lo + (hi - lo) // 2)

        self.origins = {f: organic_center(getattr(state, f))
                        for f in TAG_I64_FIELDS}

    def narrow(self, mut: dict):
        """Rebase the int64 fields of a mutable-carry dict to int32;
        returns (narrowed dict, all-windows-held scalar).  Dead lanes
        rebase as zero offsets and never affect the fit."""
        ok = jnp.bool_(True)
        out = dict(mut)
        for f in TAG_I64_FIELDS:
            v = jnp.where(self.live0, mut[f], self.origins[f])
            v32, o = kernels.rebase32(v, self.origins[f])
            out[f] = v32
            ok = ok & o
        return out, ok

    def widen(self, mut32: dict) -> dict:
        """Inverse of :meth:`narrow` for live lanes; dead lanes widen
        to their origin -- garbage, but every consumer masks them by
        candidacy, and :meth:`restore` puts the real values back."""
        out = dict(mut32)
        for f in TAG_I64_FIELDS:
            out[f] = kernels.restore64(mut32[f], self.origins[f])
        return out

    def gate(self, dead, mut: dict, new_mut: dict, outs):
        """The per-batch fallback gate every epoch scan shares: narrow
        the post-batch state, and when it does not fit (or an earlier
        batch already tripped) zero this batch's outputs and keep the
        carry at the last good state.

        ``outs`` is a sequence of (value, fallback-fill) pairs in the
        scan's output order; returns ``(mut, dead, good, trip,
        gated_values)``."""
        new32, fit = self.narrow(new_mut)
        good = ~dead & fit
        trip = ~dead & ~fit
        vals = tuple(jnp.where(good, v, f) for v, f in outs)
        mut = {f: jnp.where(good, new32[f], mut[f]) for f in new32}
        return mut, dead | ~fit, good, trip, vals

    def restore(self, mut32: dict, mut0_64: dict, ok0) -> dict:
        """Exit state: widened live lanes, exact entry values for dead
        lanes (never written mid-epoch), and -- when the ENTRY state
        already failed to narrow -- the input state untouched."""
        out = self.widen(mut32)
        for f in out:
            keep = (self.live0 & ok0) if f in TAG_I64_FIELDS else ok0
            out[f] = jnp.where(keep, out[f], mut0_64[f])
        return out


class PrefixEpoch(NamedTuple):
    """M flat prefix batches' output, compact for one readback."""

    state: EngineState     # after ALL committed prefixes
    count: jnp.ndarray     # int32[M] decisions committed per batch
    guards_ok: jnp.ndarray  # bool[M]
    slot: jnp.ndarray      # int32[M, k] serial-order winners (-1 pad)
    phase: jnp.ndarray     # int8[M, k]  0 reservation / 1 weight
    cost: jnp.ndarray      # int32[M, k]
    lb: jnp.ndarray        # bool[M, k]  limit-break serves (Allow)
    metrics: jnp.ndarray   # int64[NUM_METRICS] (zeros unless
    #                        with_metrics; rides the same readback)
    # telemetry plane (None unless the caller passed an accumulator):
    hists: object = None   # int64[NUM_HISTS, NUM_BUCKETS+1]
    ledger: object = None  # int64[N, LED_COLS]
    flight: object = None  # obs.flight.FlightState
    slo: object = None     # int64[N, W_FIELDS] window block (obs.slo)
    prov: object = None    # obs.provenance.ProvBlock


def _batch_metrics(met, st: EngineState, *, count, resv, prop, lb,
                   guards_ok, rebase_fallback=False, live=True,
                   ladder_levels_used=0, ladder_base_decisions=0,
                   ladder_fallbacks=0, wheel_occ_hwm=0,
                   wheel_reslots=0):
    """Fold one batch's contribution into the epoch metrics vector --
    pure reductions over arrays the batch already materialized, so the
    decision stream cannot be perturbed.  A stall is a batch that
    committed nothing while work sat queued (every queued head capped
    by its limit/reservation tag).  ``rebase_fallback`` marks an int32
    tag-carry window trip (tag_width=32 epochs only); ``live`` is
    False for the DEAD batches after such a trip -- their forced-zero
    counts are not scheduler stalls, their speculative (discarded)
    state must not feed the ring high-water mark, and their guard
    outcomes would re-count one frozen speculation every remaining
    batch."""
    queued = jnp.any(st.active & (st.depth > 0))
    stall = (count == 0) & queued & live
    hwm = jnp.where(live, jnp.max(st.depth), 0)
    return obsdev.metrics_combine(met, obsdev.metrics_delta(
        decisions=count.astype(jnp.int64),
        resv=resv.astype(jnp.int64), prop=prop.astype(jnp.int64),
        limit_break=lb.astype(jnp.int64),
        stalls=stall.astype(jnp.int64),
        ring_hwm=hwm.astype(jnp.int64),
        guard_trips=(~guards_ok & live).astype(jnp.int64),
        rebase_fallbacks=jnp.asarray(rebase_fallback,
                                     jnp.int64),
        cal_ladder_levels_used=ladder_levels_used,
        cal_ladder_base_decisions=ladder_base_decisions,
        cal_ladder_fallbacks=ladder_fallbacks,
        wheel_occ_hwm=wheel_occ_hwm,
        wheel_reslots=wheel_reslots))


def _telemetry_delta(st_post: EngineState, now, cls, key, served_pc,
                     resv_pc, lb_pc, count, with_hists: bool,
                     with_ledger: bool, cost_pc=None,
                     with_slo: bool = False):
    """One batch/level's telemetry contribution (``obs.histograms`` /
    ``obs.slo``): pure reductions over the entry classification the
    batch already computed and the pre/post depth delta, so the
    decision stream cannot be perturbed.  Returns ``(hist_delta |
    None, ledger_delta | None, slo_delta | None)``; the caller folds
    them gated on batch liveness (the tag32 dead-batch rule, exactly
    like ``_batch_metrics``).

    Tardiness/latency are ENTRY-HEAD observations: ``max(now - key,
    0)`` against the committed unit's unified entry key -- the
    reservation deadline for class-0 entries, the effective proportion
    tag for class-1/2 entries (0 = served at/ahead of its virtual
    tag).  The stall observation is the time until the earliest queued
    head becomes eligible, read from the post-batch state.
    ``cost_pc`` (required with ``with_slo``) is the per-client
    delivered cost the batch committed -- the window block's cost
    column shares the ledger's entry-head tardiness semantics, so the
    windowed-vs-cumulative cross-check can hold exactly."""
    m = served_pc > 0
    tard = jnp.maximum(jnp.asarray(now, jnp.int64) - key, 0)
    resv_entry = m & (cls == CLS_RESV)
    w_entry = m & (cls >= CLS_WEIGHT) & (cls < CLS_NONE)
    hd = ld = sd = None
    if with_hists:
        hd = obshist.hist_zero()
        hd = obshist.hist_observe(hd, obshist.HIST_DECISION_LATENCY,
                                  tard, w_entry)
        hd = obshist.hist_observe(hd, obshist.HIST_RESV_TARDINESS,
                                  tard, resv_entry)
        queued = st_post.active & (st_post.depth > 0)
        stalled = (count == 0) & jnp.any(queued)
        next_elig = jnp.min(jnp.where(
            queued, jnp.minimum(st_post.head_resv, st_post.head_limit),
            MAX_TAG))
        hd = obshist.hist_observe_scalar(
            hd, obshist.HIST_LIMIT_STALL,
            jnp.maximum(next_elig - now, 0), stalled)
        hd = obshist.hist_observe_scalar(
            hd, obshist.HIST_COMMIT_SIZE, count.astype(jnp.int64), 1)
    if with_ledger or with_slo:
        t = jnp.where(resv_entry, tard, 0)
    if with_ledger:
        ld = jnp.stack([served_pc.astype(jnp.int64),
                        resv_pc.astype(jnp.int64),
                        lb_pc.astype(jnp.int64), t, t], axis=1)
    if with_slo:
        assert cost_pc is not None, \
            "the SLO window block needs the per-client delivered cost"
        tardy = (resv_entry & (tard > 0)).astype(jnp.int64)
        sd = obsslo.window_delta(served_pc, cost_pc, resv_pc, tardy,
                                 lb_pc, t)
    return hd, ld, sd


def _tele_init(state: EngineState, hists, ledger, flight,
               slo=None, prov=None) -> dict:
    """Normalize the optional telemetry accumulators into the tele
    carry dict (presence of a key IS the static on-flag)."""
    tele = {}
    if hists is not None:
        tele["h"] = jnp.asarray(hists, dtype=jnp.int64)
    if ledger is not None:
        ledger = jnp.asarray(ledger, dtype=jnp.int64)
        assert ledger.shape == (state.capacity, obshist.LED_COLS), \
            f"ledger shape {ledger.shape} != " \
            f"({state.capacity}, {obshist.LED_COLS})"
        tele["l"] = ledger
    if flight is not None:
        tele["f"] = flight
    if slo is not None:
        slo = jnp.asarray(slo, dtype=jnp.int64)
        assert slo.shape == (state.capacity, obsslo.W_FIELDS), \
            f"slo window shape {slo.shape} != " \
            f"({state.capacity}, {obsslo.W_FIELDS})"
        tele["s"] = slo
    if prov is not None:
        assert prov.last_served.shape == (state.capacity,), \
            f"prov last_served shape {prov.last_served.shape} != " \
            f"({state.capacity},)"
        tele["p"] = prov
    return tele


def _tele_fold(tele: dict, hd, ld, live, sd=None) -> dict:
    """Fold one batch's histogram/ledger/window deltas, gated on
    liveness."""
    out = dict(tele)
    if "h" in tele:
        out["h"] = obshist.hist_fold(tele["h"], hd, live)
    if "l" in tele:
        out["l"] = obshist.ledger_fold(tele["l"], ld, live)
    if "s" in tele:
        out["s"] = obsslo.window_fold(tele["s"], sd, live)
    return out


def _tele_entry_fold(tele: dict, st: EngineState, post_state,
                     now, allow: bool, count, live, cost_pc=None,
                     margins=None):
    """The shared prefix/chain telemetry fold: batch-entry
    classification, depth-delta served counts, the entry-head
    resv/limit-break derivation, and the gated histogram/ledger/window
    fold -- ONE implementation so the two sorted engines' entry-head
    semantics cannot drift.  ``margins`` is the batch's per-record
    winner-margin array (the provenance plane's histogram feed).
    Returns ``(tele, key_e, gate_n)`` -- the entry keys and the
    limit-gated client count feed each engine's own flight record."""
    cls_e, key_e = _classify(st, now, allow)
    served_pc = (st.depth - post_state.depth).astype(jnp.int32)
    srv = served_pc > 0
    w_entry = srv & (cls_e >= CLS_WEIGHT) & (cls_e < CLS_NONE)
    hd, ld, sd = _telemetry_delta(
        post_state, now, cls_e, key_e, served_pc,
        served_pc - w_entry.astype(jnp.int32),
        (srv & (cls_e == CLS_LB)).astype(jnp.int32),
        count, "h" in tele, "l" in tele,
        cost_pc=cost_pc, with_slo="s" in tele)
    has_req = st.active & (st.depth > 0)
    elig = cls_e != CLS_NONE
    gate_n = jnp.sum(has_req & ~elig).astype(jnp.int64)
    out = _tele_fold(tele, hd, ld, live, sd)
    if "p" in tele:
        newp = obsprov.prov_observe(
            tele["p"], now=now, elig=elig, gated=has_req & ~elig,
            win_cls=jnp.min(jnp.where(elig, cls_e, CLS_NONE)),
            served_pc=served_pc, margins=margins)
        out["p"] = obsprov.prov_select(live, newp, tele["p"])
    return out, key_e, gate_n


def _tele_flight(tele: dict, slot, cls, tag, cost, live,
                 margin=None, gate=None) -> dict:
    if "f" not in tele:
        return tele
    out = dict(tele)
    out["f"] = obsflight.flight_record(tele["f"], slot, cls, tag,
                                       cost, live=live,
                                       margin=margin, gate=gate)
    return out


def scan_prefix_epoch(state: EngineState, now, m: int, k: int, *,
                      anticipation_ns: int,
                      allow_limit_break: bool = False,
                      with_metrics: bool = False,
                      select_impl: str = "sort",
                      tag_width: int = 64,
                      window_m: int | None = None,
                      hists=None, ledger=None,
                      flight=None, slo=None,
                      prov=None) -> PrefixEpoch:
    """Run m flat prefix-commit batches of up to k decisions on device.

    EVERY batch commits its own exact prefix, so the concatenated
    per-batch prefixes are always the serial decision stream at
    ``now``.  Batches after the workload drains commit 0 and
    spin harmlessly.  Callers MUST check ``guards_ok``: a rare global
    rebase-guard failure (creation-order spread or served cost past
    2^31) zeroes that batch and every later one without committing --
    rerun from the returned state via ``make_prefix_runner``'s serial
    fallback in that case.

    ``with_metrics`` (STATIC) accumulates the ``obs.device`` vector in
    the same scan carry; the decision stream and final state are
    bit-identical with it on or off (tests/test_obs.py).

    ``select_impl`` (STATIC, "sort"|"radix") picks the selection
    backend -- both produce bit-identical decision streams
    (tests/test_radix.py); "radix" replaces the O(N log N) full sort
    with histogram k-selection + a [k]-sized sort.

    ``tag_width`` (STATIC, 64|32): with 32 the scan carries the int64
    tag fields as int32 epoch-rebased offsets (half the loop-carried
    HBM traffic); a window trip makes that batch and every later one
    commit 0 with guards_ok False (plus one ``rebase_fallbacks``
    metric bump) -- same caller contract as the sort-key guard.

    ``window_m`` (STATIC) chunks the ring-window prefetch: the epoch
    runs ``m / window_m`` prefetch chunks of ``window_m`` batches
    each, so wide epochs (m=64) amortize per-epoch dispatch without
    growing the unrolled window-select chain past ``window_m`` rows
    (the chain's cost scales with the window width -- PROFILE.md).
    Must divide m; None = one m-row window (the original layout).

    ``hists`` / ``ledger`` / ``flight`` / ``slo`` / ``prov`` (each
    None = off; presence is the static flag) are INITIAL telemetry
    accumulators (``obs.histograms.hist_zero()`` / ``ledger_zero(N)``
    / ``obs.flight.flight_init(R)`` / ``obs.slo.window_zero(N)`` /
    ``obs.provenance.prov_init(N)`` or the previous epoch's outputs,
    so chained epochs accumulate on device with one final fetch).
    They ride the scan carry next to the metrics vector and come back
    as the epoch result's ``hists``/``ledger``/``flight``/``slo``/
    ``prov`` fields; the decision stream and final state are
    bit-identical with telemetry on or off (tests/test_telemetry.py,
    tests/test_slo.py, tests/test_provenance.py).
    """
    assert tag_width in (32, 64), tag_width
    w = m if window_m is None else min(int(window_m), m)
    assert w > 0 and m % w == 0, "window_m must divide m"
    narrow32 = tag_width == 32
    invariant = {f: getattr(state, f) for f in _EPOCH_INVARIANT}
    mutable0_64 = {f: getattr(state, f) for f in _EPOCH_MUTABLE}
    met0 = obsdev.metrics_zero()
    tele0 = _tele_init(state, hists, ledger, flight, slo, prov)
    need_class = bool(tele0)
    if narrow32:
        tc = _TagCarry32(state)
        mutable0, ok0 = tc.narrow(mutable0_64)
        if with_metrics:
            met0 = obsdev.metrics_combine(met0, obsdev.metrics_delta(
                rebase_fallbacks=(~ok0).astype(jnp.int64)))
        carry0 = (mutable0, met0, tele0, ~ok0)
    else:
        carry0 = (mutable0_64, met0, tele0)

    def body(window, carry, _):
        if narrow32:
            mut, met, tele, dead = carry
            st = EngineState(**invariant, **tc.widen(mut))
        else:
            mut, met, tele = carry
            st = EngineState(**invariant, **mut)
        batch = speculate_prefix_batch(
            st, now, k, anticipation_ns=anticipation_ns,
            heads=_window_heads(st, window),
            allow_limit_break=allow_limit_break,
            select_impl=select_impl)
        count = batch.count
        guards = batch.guards_ok
        slot = batch.decisions.slot
        phase = batch.decisions.phase.astype(jnp.int8)
        cost = batch.decisions.cost.astype(jnp.int32)
        lb = batch.decisions.limit_break
        new_mut = {f: getattr(batch.state, f) for f in _EPOCH_MUTABLE}
        trip = jnp.bool_(False)
        good = jnp.bool_(True)
        if narrow32:
            mut, dead, good, trip, \
                (count, guards, slot, phase, cost, lb) = tc.gate(
                    dead, mut, new_mut,
                    [(count, 0), (guards, False), (slot, -1),
                     (phase, jnp.int8(0)), (cost, 0), (lb, False)])
        else:
            mut = new_mut
        out = (count, guards, slot, phase, cost, lb)
        if with_metrics:
            served = slot >= 0
            resv = jnp.sum(served & (phase == 0))
            met = _batch_metrics(
                met, batch.state, count=count, resv=resv,
                prop=count - resv, lb=jnp.sum(lb),
                guards_ok=batch.guards_ok, rebase_fallback=trip,
                live=good)
        if need_class:
            # entry classification recomputed for telemetry only (a
            # cheap dense pass; the decision stream is untouched)
            tele, key_e, gate_n = _tele_entry_fold(
                tele, st, batch.state, now, allow_limit_break,
                batch.count, good, cost_pc=batch.cost_pc,
                margins=batch.margins)
            tele = _tele_flight(
                tele, slot,
                phase.astype(jnp.int64) + lb.astype(jnp.int64),
                jnp.take(key_e, jnp.maximum(slot, 0)), cost, good,
                margin=batch.margins, gate=gate_n)
        carry = (mut, met, tele, dead) if narrow32 \
            else (mut, met, tele)
        return carry, out

    def run_chunk(carry, _):
        mut64 = tc.widen(carry[0]) if narrow32 else carry[0]
        st_c = EngineState(**invariant, **mut64)
        window = ring_window(st_c, w)
        return lax.scan(functools.partial(body, window), carry, None,
                        length=w)

    if w == m:
        carry, outs = run_chunk(carry0, None)
    else:
        carry, outs = lax.scan(run_chunk, carry0, None, length=m // w)
        outs = jax.tree_util.tree_map(
            lambda a: a.reshape((m,) + a.shape[2:]), outs)
    count, guards, slot, phase, cost, lb = outs
    mutable, metrics, tele = carry[0], carry[1], carry[2]
    if narrow32:
        state = EngineState(**invariant,
                            **tc.restore(mutable, mutable0_64, ok0))
    else:
        state = EngineState(**invariant, **mutable)
    return PrefixEpoch(state=state, count=count, guards_ok=guards,
                       slot=slot, phase=phase, cost=cost, lb=lb,
                       metrics=metrics, hists=tele.get("h"),
                       ledger=tele.get("l"), flight=tele.get("f"),
                       slo=tele.get("s"), prov=tele.get("p"))


class ChainEpoch(NamedTuple):
    """M chained prefix batches' output, compact for one readback."""

    state: EngineState
    count: jnp.ndarray       # int32[M] decisions committed per batch
    unit_count: jnp.ndarray  # int32[M]
    guards_ok: jnp.ndarray   # bool[M]
    slot: jnp.ndarray        # int32[M, k] unit clients (-1 pad)
    cls: jnp.ndarray         # int8[M, k]  unit entry class
    length: jnp.ndarray      # int8[M, k]  unit decisions
    metrics: jnp.ndarray     # int64[NUM_METRICS] (zeros unless
    #                          with_metrics)
    # telemetry plane (None unless the caller passed an accumulator)
    hists: object = None
    ledger: object = None
    flight: object = None
    slo: object = None
    prov: object = None


def scan_chain_epoch(state: EngineState, now, m: int, k: int, *,
                     chain_depth: int, anticipation_ns: int,
                     allow_limit_break: bool = False,
                     use_pallas: bool | None = None,
                     with_metrics: bool = False,
                     select_impl: str = "sort",
                     tag_width: int = 64,
                     hists=None, ledger=None,
                     flight=None, slo=None,
                     prov=None) -> ChainEpoch:
    """Run m chained prefix batches on device.  Each batch prefetches
    its own ``chain_depth``-row ring window (one barrel-shift ring
    pass per batch; a shared per-epoch window would need m *
    chain_depth rows of unrolled selects, which costs more than the
    rotate at chain depths > 1).  ``select_impl`` / ``tag_width`` /
    the ``hists``/``ledger``/``flight`` telemetry accumulators as in
    :func:`scan_prefix_epoch` (flight records here are per UNIT, the
    cost column carrying the unit's decision count)."""
    assert chain_depth <= state.ring_capacity
    assert tag_width in (32, 64), tag_width
    narrow32 = tag_width == 32
    invariant = {f: getattr(state, f) for f in _EPOCH_INVARIANT}
    mutable0_64 = {f: getattr(state, f) for f in _EPOCH_MUTABLE}
    met0 = obsdev.metrics_zero()
    tele0 = _tele_init(state, hists, ledger, flight, slo, prov)
    need_class = bool(tele0)
    if narrow32:
        tc = _TagCarry32(state)
        mutable0, ok0 = tc.narrow(mutable0_64)
        if with_metrics:
            met0 = obsdev.metrics_combine(met0, obsdev.metrics_delta(
                rebase_fallbacks=(~ok0).astype(jnp.int64)))
        carry0 = (mutable0, met0, tele0, ~ok0)
    else:
        carry0 = (mutable0_64, met0, tele0)

    def body(carry, _):
        if narrow32:
            mut, met, tele, dead = carry
            st = EngineState(**invariant, **tc.widen(mut))
        else:
            mut, met, tele = carry
            st = EngineState(**invariant, **mut)
        win = ring_window(st, chain_depth, use_pallas=use_pallas)
        batch = speculate_chain_batch(
            st, now, k, chain_depth=chain_depth,
            anticipation_ns=anticipation_ns,
            heads=(win.arr, win.cost),
            allow_limit_break=allow_limit_break,
            select_impl=select_impl)
        count, ucount = batch.count, batch.unit_count
        guards = batch.guards_ok
        slot = batch.slot
        cls = batch.cls.astype(jnp.int8)
        length = batch.length.astype(jnp.int8)
        new_mut = {f: getattr(batch.state, f) for f in _EPOCH_MUTABLE}
        trip = jnp.bool_(False)
        good = jnp.bool_(True)
        if narrow32:
            mut, dead, good, trip, \
                (count, ucount, guards, slot, cls, length) = tc.gate(
                    dead, mut, new_mut,
                    [(count, 0), (ucount, 0), (guards, False),
                     (slot, -1), (cls, jnp.int8(CLS_NONE)),
                     (length, jnp.int8(0))])
        else:
            mut = new_mut
        out = (count, ucount, guards, slot, cls, length)
        if with_metrics:
            units = slot >= 0
            # a unit's entry serve is weight-phase iff class >= 1; its
            # induced serves are all constraint-phase
            prop = jnp.sum(jnp.where(units, (cls >= CLS_WEIGHT)
                                     .astype(jnp.int64), 0))
            met = _batch_metrics(
                met, batch.state, count=count,
                resv=count.astype(jnp.int64) - prop, prop=prop,
                lb=jnp.sum(units & (cls >= CLS_LB)),
                guards_ok=batch.guards_ok, rebase_fallback=trip,
                live=good)
        if need_class:
            tele, key_e, gate_n = _tele_entry_fold(
                tele, st, batch.state, now, allow_limit_break,
                batch.count, good, cost_pc=batch.cost_pc,
                margins=batch.margins)
            tele = _tele_flight(
                tele, slot, cls.astype(jnp.int64),
                jnp.take(key_e, jnp.maximum(slot, 0)),
                length.astype(jnp.int64), good,
                margin=batch.margins, gate=gate_n)
        carry = (mut, met, tele, dead) if narrow32 \
            else (mut, met, tele)
        return carry, out

    carry, (count, units, guards, slot, cls, length) = \
        lax.scan(body, carry0, None, length=m)
    mutable, metrics, tele = carry[0], carry[1], carry[2]
    if narrow32:
        state = EngineState(**invariant,
                            **tc.restore(mutable, mutable0_64, ok0))
    else:
        state = EngineState(**invariant, **mutable)
    return ChainEpoch(state=state, count=count, unit_count=units,
                      guards_ok=guards, slot=slot, cls=cls,
                      length=length, metrics=metrics,
                      hists=tele.get("h"), ledger=tele.get("l"),
                      flight=tele.get("f"), slo=tele.get("s"),
                      prov=tele.get("p"))


# Module-level jit cache for the host-orchestrated prefix runner (the
# engine/queue.py convention, compile-plane-instrumented): repeated
# make_prefix_runner calls at one static config share one compiled
# attempt/exact pair instead of re-tracing per runner.
_RUNNER_JIT_CACHE: dict = {}


def _runner_jit(key: tuple, make):
    if key not in _RUNNER_JIT_CACHE:
        from ..obs import compile_plane as _cplane
        _RUNNER_JIT_CACHE[key] = _cplane.instrumented_jit(
            make(), cache="fastpath.runner", entry=key)
    return _RUNNER_JIT_CACHE[key]


def make_prefix_runner(k: int, *, anticipation_ns: int = 0,
                       allow_limit_break: bool = False,
                       select_impl: str = "sort"):
    """Host-orchestrated prefix runner: (state, now) -> (state,
    decisions, n_committed).  The serial engine is needed only when the
    global rebase guards fail (creation-order spread or a served cost
    past 2^31 -- never observed in practice); a zero count with guards
    intact means nothing is eligible at ``now`` (serial FUTURE/NONE).
    """
    attempt = _runner_jit(
        ("attempt", k, anticipation_ns, allow_limit_break,
         select_impl),
        lambda: functools.partial(
            speculate_prefix_batch, k=k,
            anticipation_ns=anticipation_ns,
            allow_limit_break=allow_limit_break,
            select_impl=select_impl))
    exact = _runner_jit(
        ("exact", k, anticipation_ns, allow_limit_break),
        lambda: lambda s, t: kernels.engine_run(
            s, t, k, allow_limit_break=allow_limit_break,
            anticipation_ns=anticipation_ns, advance_now=False))

    def run(state: EngineState, now):
        batch = attempt(state, now)
        if not bool(batch.guards_ok):
            st, _, decs = exact(state, now)
            d = jax.device_get(decs)
            return st, decs, int((d.type == RETURNING).sum())
        return batch.state, batch.decisions, int(batch.count)

    return run


# ----------------------------------------------------------------------
# calendar commit: sortless window batches
# ----------------------------------------------------------------------
#
# The sort-based prefix batch tops out when re-entries undercut the
# sorted tail: a Zipf weight-64 client re-enters every 2*winv_64 ns of
# proportion-tag space, so a single sort commits only the entries
# inside that window (~2.5k of 100k at the cfg4 steady state).  The
# calendar batch removes the sort entirely, from two structural facts:
#
#  1. The serial engine's SERVED unified keys are nondecreasing: it
#     always serves the global minimum, and a serve's re-entry key is
#     above its entry key (per-client tags are monotone under serves;
#     the one exception -- a weight serve's reservation-debt reduction
#     dropping the client into class 0 -- is absorbed into the serving
#     UNIT exactly as in the chained batches, and unit ENTRY keys are
#     nondecreasing per client, enforced below).
#  2. Therefore, for ANY boundary B, the set {serves whose unit entry
#     key < B} is exactly a prefix of the serial order -- computable
#     PER CLIENT by iterating its own tag recurrence, independent of
#     every other client.
#
# A client that cannot be followed past some point (serve-step budget
# exhausted, a unit's induced-serve chain cut mid-way, a non-monotone
# next entry) contributes its first unfollowable entry key as a STOP;
# B_eff = min over stops, and two dense passes (measure stops, then
# commit gated on B_eff) yield up to `steps` decisions per client per
# batch with no [k] cap and no 32-bit rebase guards (keys pack into a
# 58-bit per-class window that never clamps in practice; clamping is
# monotone and therefore only conservative).  The batch emits
# per-client counts, not an ordered stream: the committed SET plus the
# final state is exact (differentially pinned vs the serial engine);
# callers needing the ordered stream use the sort-based batches.

_CAL_BIAS = jnp.int64(1) << 57
_CAL_MASK = (jnp.int64(1) << 58) - 1


class CalendarBatch(NamedTuple):
    """Result of one calendar-commit batch."""

    state: EngineState
    count: jnp.ndarray        # int32 committed decisions
    resv_count: jnp.ndarray   # int32 constraint-phase decisions
    units: jnp.ndarray        # int32[N] committed units per client
    served: jnp.ndarray       # int32[N] committed decisions per client
    served_resv: jnp.ndarray  # int32[N] constraint decisions
    lb: jnp.ndarray           # int32[N] limit-break entries (Allow)
    progress_ok: jnp.ndarray  # bool: count>0 or no candidate existed
    served_cost: object = None  # int64[N] delivered cost per client
    margin: object = None     # int64[N] boundary-distance margin per
    #                           served client: B_eff minus the
    #                           client's LAST unit-entry pack, ns for
    #                           same-class keys (-1 = not served or
    #                           no finite boundary; obs.provenance)


def _cal_pack(cls, key, kresv, kprop1, kprop2):
    origin = jnp.where(cls == CLS_RESV, kresv,
                       jnp.where(cls == CLS_WEIGHT, kprop1, kprop2))
    rel = jnp.clip(key - origin + _CAL_BIAS, 0, _CAL_MASK)
    return jnp.where(cls == CLS_NONE, jnp.int64(KEY_INF),
                     (cls.astype(jnp.int64) << 58) | rel)


def _calendar_pass(state: EngineState, now, arr_rows, cost_rows,
                   allow: bool, anticipation_ns: int,
                   kresv, kprop1, kprop2, b_eff):
    """One dense pass of per-client serve iteration, as a lax.scan
    over the step axis (an unrolled step loop at steps=32 exploded
    TPU compile time).

    With ``b_eff`` None: measure mode -- serve everything followable
    and return the per-client STOP pack (KEY_INF when the client ran
    out of work).  With ``b_eff`` a scalar: commit mode -- serves gate
    on the unit entry pack being strictly below it; returns the final
    dense state fields and the per-client counters.

    Readiness is classified as ``limit <= now`` at every step: a
    stored ready flag implies it under the monotonic-now restriction
    (promotion happened at some now' <= now with limit <= now', and
    pops clear the flag), so the stored bit adds nothing here."""
    n = state.capacity

    carry0 = dict(
        h_resv=state.head_resv, h_prop=state.head_prop,
        h_limit=state.head_limit, h_arr=state.head_arrival,
        h_cost=state.head_cost, h_rho=state.head_rho,
        p_resv=state.prev_resv, p_prop=state.prev_prop,
        p_limit=state.prev_limit, p_arr=state.prev_arrival,
        depth=state.depth,
        qadv=jnp.zeros_like(state.q_head),
        cost=jnp.zeros_like(state.head_cost),
        alive=jnp.ones((n,), dtype=bool),
        in_unit=jnp.zeros((n,), dtype=bool),
        stop_pk=jnp.full((n,), jnp.int64(KEY_INF)),
        prev_pk=jnp.full((n,), jnp.int64(-1)),
        unit_cls=jnp.zeros((n,), dtype=jnp.int32),
        units=jnp.zeros((n,), dtype=jnp.int32),
        served=jnp.zeros((n,), dtype=jnp.int32),
        served_resv=jnp.zeros((n,), dtype=jnp.int32),
        lb=jnp.zeros((n,), dtype=jnp.int32),
    )

    def step(c, row):
        narr, ncost = row
        has = state.active & (c["depth"] > 0)
        cls, key = _unified_class(
            now, has, c["h_resv"], c["h_limit"] <= now, c["h_prop"],
            c["h_prop"] + state.prop_delta, allow)
        pk = _cal_pack(cls, key, kresv, kprop1, kprop2)

        at_boundary = ~c["in_unit"]
        cand = cls != CLS_NONE
        alive = c["alive"]
        nonmono = alive & at_boundary & cand & (pk < c["prev_pk"])
        stop_pk = c["stop_pk"]
        if b_eff is None:
            stop_pk = jnp.where(
                nonmono, jnp.minimum(stop_pk, c["prev_pk"]), stop_pk)
        alive = alive & ~(at_boundary & (~cand | nonmono))
        start = alive & at_boundary & cand
        if b_eff is not None:
            start = start & (pk < b_eff)
            alive = alive & ~(at_boundary & ~start)

        serve = start | (c["in_unit"] & alive)
        phase1 = start & (cls >= CLS_WEIGHT)

        nr, np_, nl = _make_tag(
            c["h_resv"], c["h_prop"], c["h_limit"], c["h_arr"],
            state.resv_inv, state.weight_inv, state.limit_inv,
            state.cur_delta, state.cur_rho, narr, ncost,
            anticipation_ns)
        off = jnp.where(phase1,
                        state.resv_inv * (c["h_cost"] + c["h_rho"]),
                        jnp.zeros_like(c["h_resv"]))
        new_depth = c["depth"] - 1
        has_more = new_depth > 0
        upd = serve
        updh = serve & has_more
        new_h_resv = nr - off
        pr = jnp.where(has_more, _fold_prev(c["p_resv"], nr),
                       c["p_resv"]) - off
        pp = jnp.where(has_more, _fold_prev(c["p_prop"], np_),
                       c["p_prop"])
        pl_ = jnp.where(has_more, _fold_prev(c["p_limit"], nl),
                        c["p_limit"])

        chains_cls = (cls == CLS_WEIGHT) | (cls == CLS_LB)
        unit_cls = jnp.where(start, cls, c["unit_cls"])
        cont_cls = (unit_cls == CLS_WEIGHT) | (unit_cls == CLS_LB)

        new = dict(
            h_resv=jnp.where(updh, new_h_resv, c["h_resv"]),
            h_prop=jnp.where(updh, np_, c["h_prop"]),
            h_limit=jnp.where(updh, nl, c["h_limit"]),
            h_arr=jnp.where(updh, narr, c["h_arr"]),
            h_cost=jnp.where(updh, ncost, c["h_cost"]),
            h_rho=jnp.where(updh, state.cur_rho, c["h_rho"]),
            p_resv=jnp.where(upd, pr, c["p_resv"]),
            p_prop=jnp.where(upd, pp, c["p_prop"]),
            p_limit=jnp.where(upd, pl_, c["p_limit"]),
            p_arr=jnp.where(updh, narr, c["p_arr"]),
            depth=jnp.where(upd, new_depth,
                            c["depth"]).astype(jnp.int32),
            qadv=(c["qadv"] + updh).astype(jnp.int32),
            # delivered cost: the head served at this step is the
            # CURRENT h_cost (the SLO window block's cost column)
            cost=c["cost"] + jnp.where(serve, c["h_cost"],
                                       jnp.int64(0)),
            alive=alive,
            in_unit=serve & cont_cls & has_more & (new_h_resv <= now),
            stop_pk=stop_pk,
            prev_pk=jnp.where(start, pk, c["prev_pk"]),
            unit_cls=unit_cls,
            units=c["units"] + start,
            served=c["served"] + serve,
            served_resv=c["served_resv"]
            + ((start & (cls == CLS_RESV)) | (serve & c["in_unit"])),
            lb=c["lb"] + (start & (cls >= CLS_LB)),
        )
        return new, None

    rows = (jnp.stack(arr_rows), jnp.stack(cost_rows))
    c, _ = lax.scan(step, carry0, rows)

    if b_eff is None:
        # post-loop stops: a chain still mid-unit cannot be followed
        # (exclude its whole unit); an alive client at a unit boundary
        # stops at its NEXT entry key.
        stop_pk = jnp.where(c["in_unit"],
                            jnp.minimum(c["stop_pk"], c["prev_pk"]),
                            c["stop_pk"])
        has = state.active & (c["depth"] > 0)
        cls, key = _unified_class(
            now, has, c["h_resv"], c["h_limit"] <= now, c["h_prop"],
            c["h_prop"] + state.prop_delta, allow)
        pk = _cal_pack(cls, key, kresv, kprop1, kprop2)
        boundary_stop = c["alive"] & ~c["in_unit"] & (cls != CLS_NONE)
        nonmono_next = boundary_stop & (pk < c["prev_pk"])
        stop_pk = jnp.where(
            boundary_stop,
            jnp.minimum(stop_pk,
                        jnp.where(nonmono_next, c["prev_pk"], pk)),
            stop_pk)
        return stop_pk

    fields = dict(head_resv=c["h_resv"], head_prop=c["h_prop"],
                  head_limit=c["h_limit"], head_arrival=c["h_arr"],
                  head_cost=c["h_cost"], head_rho=c["h_rho"],
                  prev_resv=c["p_resv"], prev_prop=c["p_prop"],
                  prev_limit=c["p_limit"], prev_arrival=c["p_arr"],
                  depth=c["depth"])
    return (fields, c["qadv"], c["units"], c["served"],
            c["served_resv"], c["lb"], c["prev_pk"], c["unit_cls"],
            c["cost"])


def _calendar_batch_core(state: EngineState, now, arr_rows, cost_rows,
                         *, anticipation_ns: int,
                         allow_limit_break: bool,
                         origins=None, stop_min=None):
    """The measure + boundary + commit + promote pipeline of one
    calendar batch, given the prefetched window rows.  Shared by
    :func:`calendar_batch` (one boundary per launch) and the bucketed
    ladder (L fused boundaries per launch).

    The boundary is the stop-key distribution's FIRST order statistic
    -- what ``kernels.radix_kth_key(stop_pk, 1)`` computes -- read as
    a plain ``jnp.min``: the same value for 16x fewer dense passes,
    and this stack's CPU backend miscompiles the histogram walk inside
    the sharded device sim (deterministic compiler SIGFPE, see
    tests/test_calendar_bucketed.py's device-sim note).  The histogram
    rounds proper serve where ranks beyond 1 are genuinely needed: the
    quantile planner (:func:`calendar_stop_ladder`).

    ``origins`` injects precomputed ``(kresv, kprop1, kprop2,
    any_cand)`` pack origins -- the wheel ladder reads them from its
    maintained bucket index in O(buckets) instead of the dense
    per-class mins here.  ``stop_min`` likewise replaces the dense
    ``jnp.min`` boundary with the wheel's occupancy-min-scan.  Both
    must be BIT-IDENTICAL to the dense reductions they replace (the
    wheel exactness argument, see the kernels wheel section).

    Returns ``(CalendarBatch, b_eff, stop_pk)``."""
    if origins is None:
        cls0, key0 = _classify(state, now, allow_limit_break)
        kresv = jnp.min(jnp.where(cls0 == CLS_RESV, key0, KEY_INF))
        kprop1 = jnp.min(jnp.where(cls0 == CLS_WEIGHT, key0, KEY_INF))
        kprop2 = jnp.min(jnp.where(cls0 == CLS_LB, key0, KEY_INF))
        any_cand = jnp.any(cls0 != CLS_NONE)
    else:
        kresv, kprop1, kprop2, any_cand = origins

    stop_pk = _calendar_pass(state, now, arr_rows, cost_rows,
                             allow_limit_break, anticipation_ns,
                             kresv, kprop1, kprop2, None)
    b_eff = jnp.min(stop_pk) if stop_min is None else stop_min(stop_pk)
    (fields, qadv, units, served, served_resv, lb, last_pk,
     last_cls, cost_pc) = _calendar_pass(
         state, now, arr_rows, cost_rows, allow_limit_break,
         anticipation_ns, kresv, kprop1, kprop2, b_eff)

    did = served > 0
    popped = did & (qadv > 0)

    def pick(pred, new, old):
        return jnp.where(pred, new, old)

    new_state = state._replace(
        depth=pick(did, fields["depth"], state.depth),
        q_head=pick(popped,
                    (state.q_head + qadv) % state.ring_capacity,
                    state.q_head).astype(jnp.int32),
        head_resv=pick(popped, fields["head_resv"], state.head_resv),
        head_prop=pick(popped, fields["head_prop"], state.head_prop),
        head_limit=pick(popped, fields["head_limit"],
                        state.head_limit),
        head_arrival=pick(popped, fields["head_arrival"],
                          state.head_arrival),
        head_cost=pick(popped, fields["head_cost"], state.head_cost),
        head_rho=pick(popped, fields["head_rho"], state.head_rho),
        head_ready=state.head_ready & ~did,
        prev_resv=pick(did, fields["prev_resv"], state.prev_resv),
        prev_prop=pick(did, fields["prev_prop"], state.prev_prop),
        prev_limit=pick(did, fields["prev_limit"], state.prev_limit),
        prev_arrival=pick(popped, fields["prev_arrival"],
                          state.prev_arrival),
    )

    # stored-flag parity (promote loop): the batch's LAST serial
    # decision is the unit with the max entry pack (ties by creation
    # order); if its class is >= 1, its entry ran the final promote
    # pass, whose only unseen head is the one that unit's own chain
    # popped into place.
    lp = jnp.where(did, last_pk, jnp.int64(-1))
    maxpk = jnp.max(lp)
    tied = did & (lp == maxpk)
    excl = jnp.argmax(jnp.where(tied, state.order,
                                jnp.int64(-1))).astype(jnp.int32)
    cls_last = jnp.max(jnp.where(tied, last_cls, -1))
    do_promote = jnp.any(did) & (cls_last >= CLS_WEIGHT)
    has_req_after = new_state.active & (new_state.depth > 0)
    promoted = new_state.head_ready | \
        (has_req_after & (new_state.head_limit <= now))
    promoted = promoted & (
        jnp.arange(state.capacity, dtype=jnp.int32) != excl)
    new_state = new_state._replace(head_ready=jnp.where(
        do_promote, promoted, new_state.head_ready))

    count = jnp.sum(served).astype(jnp.int32)
    # boundary-distance margin (obs.provenance): how much headroom
    # B_eff left each served client's LAST unit entry -- the calendar
    # analog of the sorted engines' runner-up margin (the boundary IS
    # the first unfollowable competitor).  Dead code unless a
    # provenance/flight consumer reads it (XLA DCE).
    margin = jnp.where((served > 0) & (b_eff < jnp.int64(KEY_INF)),
                       b_eff - last_pk, jnp.int64(-1))
    batch = CalendarBatch(
        state=new_state, count=count,
        resv_count=jnp.sum(served_resv).astype(jnp.int32),
        units=units, served=served, served_resv=served_resv, lb=lb,
        progress_ok=(count > 0) | ~any_cand,
        served_cost=jnp.where(served > 0, cost_pc, jnp.int64(0)),
        margin=margin)
    return batch, b_eff, stop_pk


def calendar_batch(state: EngineState, now, *, steps: int,
                   anticipation_ns: int = 0,
                   allow_limit_break: bool = False,
                   heads=None) -> CalendarBatch:
    """One calendar-commit batch: up to ``steps`` decisions PER CLIENT
    in two dense elementwise passes, no sort (see section comment).

    The committed set is exactly the serial engine's next ``count``
    decisions (differentially pinned by tests/test_prefix.py's
    calendar suite); the emission is per-client counts + final state.
    ``progress_ok`` False (count 0 with candidates present) happens
    only when the very first serial unit is unfollowable (its induced
    chain exceeds ``steps``): fall back to the serial engine."""
    assert steps <= state.ring_capacity, \
        "calendar steps exceed the ring window"
    if heads is None:
        win = ring_window(state, steps)
        heads = (win.arr, win.cost)
    arr_rows, cost_rows = _heads_rows(heads, steps)
    batch, _, _ = _calendar_batch_core(
        state, now, arr_rows, cost_rows,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break)
    return batch


# ----------------------------------------------------------------------
# bucketed calendar commits: the histogram stop-key ladder
# ----------------------------------------------------------------------
#
# The minstop boundary B_eff = min over per-client stop keys lets the
# single most conservative client truncate the whole batch: on a Zipf
# population the heavy client exhausts its `steps` budget at a low key
# while most clients could be followed far past it, so each launch
# commits one thin slab of the key space and pays a fresh dispatch for
# the next.  The bucketed ladder fuses L successive boundaries into
# ONE launch: a lax.scan over ladder levels where every level
# re-prefetches the ring window from the committed state (REFRESHED
# per-client step budgets -- the budget-stopped blocker continues from
# where it stood), measures fresh stop keys, takes the level boundary
# B_i = the stop distribution's first order statistic, and commits the
# exact serial prefix < B_i.  Level i therefore starts from exactly the
# serial state at B_{i-1}, so the concatenated committed sets are one
# serial prefix and the classical minstop exactness argument applies
# per level -- one device launch commits what previously took L full
# measure+commit batches.
#
# Why each level's boundary is its own refreshed min-stop and not a
# raw CDF quantile of the FIRST measure's stops: a stop key is a hard
# followability limit -- committing past a budget-stopped client's
# stop would emit other clients' serves the serial engine orders
# AFTER the blocker's unmeasured ones (not a prefix, not exact).
# Refreshing the budget is what discharges a stop, and only the
# level's own measure can prove it discharged.  The stop-key CDF
# ladder (``calendar_stop_ladder``, kernels.radix_quantile_ladder) is
# the PLANNER view of the same histogram: it predicts where the
# refreshed levels will land (on a skewed population the achieved
# boundaries track the stop quantiles) and prices a ladder depth L
# before running it; the commit path keeps the provable boundary.

_CAL_IMPLS = ("minstop", "bucketed", "wheel")


# ----------------------------------------------------------------------
# the timer-wheel calendar: a maintained bucket index over the tags
# ----------------------------------------------------------------------
#
# calendar_impl="wheel" keeps the bucketed ladder's commit structure
# (L refreshed-budget boundaries per launch) but replaces its dense
# O(N) reductions with O(buckets) reads of a MAINTAINED calendar
# wheel: three per-class bucket wheels (occupancy count + exact min
# key per bucket) built once per batch, then adjusted IN PLACE
# between ladder levels -- only the clients a commit actually moved
# re-slot; the rest of the population is never touched.  The level
# boundary B_i comes from a transient stop-key wheel: bucket-scatter
# the per-client stop packs and read the first occupied bucket's min
# (the occupancy-min-scan) -- the shape hand-written as the repo's
# first Pallas kernel (engine.kernels_pallas), behind the
# ``wheel_kernel`` switch with a counted XLA fallback.
#
# Exactness is inherited, not re-proven: every wheel read is
# bit-identical to the dense reduction it replaces (first occupied
# bucket's stored min == global masked min, because bucketing is
# monotone in the key -- kernels.py wheel section), so the committed
# stream, state, metrics, and telemetry equal bucketed-L and hence
# the serial engine exactly (ci.sh wheel digest gates).  The in-place
# adjust is exact because at FIXED now an unserved client's (class,
# key) cannot change across a commit: readiness is ``limit <= now``
# under monotone now (the stored head_ready bit adds nothing, see
# _calendar_pass), and the promote pass only flips stored bits that
# _ready_now already implied.  Re-slotting exactly the served clients
# therefore reproduces a full rebuild bit-for-bit (the adjust ==
# rebuild pin in tests/test_calendar_wheel.py).

_WHEEL_KERNELS = ("xla", "pallas")
_WHEEL_BUCKETS = 256
_WHEEL_SHIFT = 20        # 2^20 ns ~ 1ms fine buckets, ~268ms span
_WHEEL_STOP_SHIFT = 52   # stop packs live in [0, 2^60): 256 buckets


def _wheel_resolve(wheel_kernel: str, n: int):
    """STATIC resolution of the ``wheel_kernel`` switch: returns
    ``scan_fn(keys, slot, nb)`` matching :func:`kernels.wheel_scan`.
    "pallas" is the real kernel on TPU, or interpret mode anywhere
    under ``DMCLOCK_WHEEL_INTERPRET=1`` (the CPU parity path); any
    other "pallas" request -- off TPU, or a shape past the gridless
    kernel's lane budget -- raises instead of running the reference
    in its place."""
    if wheel_kernel not in _WHEEL_KERNELS:
        raise ValueError(f"unknown wheel_kernel {wheel_kernel!r} "
                         f"(one of {_WHEEL_KERNELS})")
    if wheel_kernel == "xla":
        return kernels.wheel_scan
    interpret = os.environ.get("DMCLOCK_WHEEL_INTERPRET") == "1"
    if not (interpret or on_tpu()):
        raise ValueError(
            'wheel_kernel="pallas" needs a TPU (or '
            "DMCLOCK_WHEEL_INTERPRET=1 for interpret mode); use "
            'wheel_kernel="xla" here')
    if not kernels_pallas.wheel_supported(n, 3 * _WHEEL_BUCKETS):
        raise ValueError(
            f'wheel_kernel="pallas" does not support n={n} clients '
            f"(gridless lane budget {kernels_pallas.MAX_LANES})")
    return functools.partial(kernels_pallas.wheel_scan_pallas,
                             interpret=interpret)


class WheelIndex(NamedTuple):
    """The maintained calendar wheel: three class wheels of
    ``_WHEEL_BUCKETS`` buckets each, concatenated on one axis
    (slot = cls * B + bucket; 3B = unslotted), plus the per-client
    slot/key mirror that makes the in-place adjust self-contained."""

    origin: jnp.ndarray   # int64 bucket-0 left edge (all 3 wheels)
    cnt: jnp.ndarray      # int32[3B] occupancy per (class, bucket)
    bmin: jnp.ndarray     # int64[3B] exact min key per bucket
    slot: jnp.ndarray     # int32[N] current slot (3B = unslotted)
    key: jnp.ndarray      # int64[N] slotted key (where slot < 3B)
    reslots: jnp.ndarray  # int64 in-place re-slots since build
    hwm: jnp.ndarray      # int64 bucket-occupancy high-water mark


def _wheel_slots(cls, key, origin):
    """(class, key) -> wheel slot; non-candidates unslot (3B)."""
    b = kernels.wheel_slot(key, origin, _WHEEL_SHIFT, _WHEEL_BUCKETS)
    return jnp.where(cls == CLS_NONE,
                     jnp.int32(3 * _WHEEL_BUCKETS),
                     cls * _WHEEL_BUCKETS + b).astype(jnp.int32)


def wheel_build(state: EngineState, now, allow: bool, *,
                scan_fn=kernels.wheel_scan) -> WheelIndex:
    """Full O(N) bucket-scatter of the entry classification -- once
    per batch; levels and API events adjust in place from here."""
    cls, key = _classify(state, now, allow)
    origin = now - (jnp.int64(_WHEEL_BUCKETS // 2)
                    << _WHEEL_SHIFT)
    slot = _wheel_slots(cls, key, origin)
    cnt, bmin, _val, _found = scan_fn(key, slot, 3 * _WHEEL_BUCKETS)
    return WheelIndex(origin=origin, cnt=cnt, bmin=bmin, slot=slot,
                      key=key, reslots=jnp.int64(0),
                      hwm=jnp.max(cnt).astype(jnp.int64))


def wheel_origins(w: WheelIndex):
    """Batch-entry pack origins read from the wheel in O(buckets):
    per class, the first occupied bucket's stored min -- bit-equal to
    the dense masked min ``_calendar_batch_core`` would compute.
    Returns ``(kresv, kprop1, kprop2, any_cand)``."""
    b = _WHEEL_BUCKETS
    vals, founds = [], []
    for c in range(3):
        v, _b0, f = kernels.wheel_nearest(w.cnt[c * b:(c + 1) * b],
                                          w.bmin[c * b:(c + 1) * b])
        vals.append(v)
        founds.append(f)
    return vals[0], vals[1], vals[2], founds[0] | founds[1] | founds[2]


def wheel_adjust(w: WheelIndex, state: EngineState, now, allow: bool,
                 moved) -> WheelIndex:
    """In-place re-slot of exactly the ``moved`` clients: decrement
    their old buckets, increment the new ones, and recompute the min
    of ONLY the touched buckets from the stored keys.  Every
    untouched bucket keeps its count and min bit-identically, so the
    result equals :func:`wheel_build` of the new state whenever
    ``moved`` covers every client whose (class, key) changed -- the
    served set of a fixed-now commit, a live QoS update's target, an
    idle re-entry, a churn re-slot (section comment; pinned by
    tests/test_calendar_wheel.py's adjust == rebuild gates)."""
    nb = 3 * _WHEEL_BUCKETS
    cls, key = _classify(state, now, allow)
    new_slot = _wheel_slots(cls, key, w.origin)
    slot2 = jnp.where(moved, new_slot, w.slot)
    key2 = jnp.where(moved, key, w.key)
    out_s = jnp.where(moved, w.slot, jnp.int32(nb))
    in_s = jnp.where(moved, slot2, jnp.int32(nb))
    cnt2 = w.cnt.at[out_s].add(jnp.int32(-1), mode="drop") \
                .at[in_s].add(jnp.int32(1), mode="drop")
    touched = jnp.zeros((nb,), bool) \
        .at[out_s].set(True, mode="drop") \
        .at[in_s].set(True, mode="drop")
    fresh = jnp.full((nb,), jnp.int64(KEY_INF)) \
        .at[slot2].min(key2, mode="drop")
    bmin2 = jnp.where(touched, fresh, w.bmin)
    changed = moved & ((slot2 != w.slot) | (key2 != w.key))
    return WheelIndex(
        origin=w.origin, cnt=cnt2, bmin=bmin2, slot=slot2, key=key2,
        reslots=w.reslots + jnp.sum(changed, dtype=jnp.int64),
        hwm=jnp.maximum(w.hwm, jnp.max(cnt2).astype(jnp.int64)))


def _wheel_stop_min(stop_pk, scan_fn):
    """The level boundary B_eff as the stop wheel's fused
    bucket-scatter + occupancy-min-scan (the Pallas kernel's shape)
    -- bit-identical to ``jnp.min(stop_pk)``: stop packs are
    non-negative and below 2^60, so 256 buckets of 2^52 cover the
    space exactly and the first occupied bucket's min IS the global
    min; all-KEY_INF distributions return KEY_INF like the dense
    min."""
    finite = stop_pk < jnp.int64(KEY_INF)
    slot = jnp.where(
        finite,
        kernels.wheel_slot(stop_pk, jnp.int64(0), _WHEEL_STOP_SHIFT,
                           _WHEEL_BUCKETS),
        jnp.int32(_WHEEL_BUCKETS))
    _cnt, _bmin, val, _found = scan_fn(stop_pk, slot, _WHEEL_BUCKETS)
    return val


class CalendarLadderBatch(NamedTuple):
    """Result of one bucketed calendar batch (L fused ladder levels).

    Totals aggregate over every level; the committed set is one serial
    prefix of ``count`` decisions (level i starts from the committed
    state of level i-1), so the differential contract is exactly
    :class:`CalendarBatch`'s with more committed per launch."""

    state: EngineState
    count: jnp.ndarray        # int32 committed decisions (all levels)
    resv_count: jnp.ndarray   # int32 constraint-phase decisions
    units: jnp.ndarray        # int32[N] committed units per client
    served: jnp.ndarray       # int32[N] committed decisions per client
    served_resv: jnp.ndarray  # int32[N] constraint decisions
    lb: jnp.ndarray           # int32[N] limit-break entries (Allow)
    progress_ok: jnp.ndarray  # bool: level 0 committed or had no
    #                           candidate (same fallback contract as
    #                           CalendarBatch.progress_ok)
    level_count: jnp.ndarray  # int32[L] decisions per ladder level
    level_bound: jnp.ndarray  # int64[L] committed boundary per level
    level_stall: jnp.ndarray  # bool[L] committed 0 with candidates
    #                           present (a mid-ladder stall wastes the
    #                           remaining levels; metric row
    #                           calendar_ladder_fallbacks)
    served_cost: object = None  # int64[N] delivered cost (all levels)


def _calendar_ladder_scan(invariant: dict, mut: dict, now, *,
                          steps: int, levels: int,
                          anticipation_ns: int, allow: bool,
                          use_pallas, with_hists: bool = False,
                          with_ledger: bool = False,
                          with_slo: bool = False,
                          prov0=None, wheel_scan_fn=None):
    """The fused ladder: a lax.scan over L levels, each a full
    window-prefetch + measure + histogram boundary + commit from the
    previous level's committed state.  Carries only the mutable epoch
    fields (the ring pair and QoS identity stay loop-invariant,
    exactly like the epoch scans).  Returns ``(mut', acc, tele_delta,
    outs, wstats)`` with ``acc`` the [N] per-client counters summed over
    levels, ``tele_delta`` the zero-based histogram/ledger deltas
    accumulated per LEVEL (so a level equals one minstop batch and
    bucketed-L telemetry equals the L-batch composition exactly; the
    caller folds the deltas gated on batch liveness), and ``outs`` the
    per-level (count, resv_count, bound, stall) stacks.  ``prov0``
    (an ``obs.provenance.ProvBlock``) threads the provenance block
    through the levels as FULL STATE (not a delta): each level
    observes its own entry classification and boundary margins, and
    the caller selects the returned block against the entry block on
    batch liveness.

    ``wheel_scan_fn`` (static, a :func:`kernels.wheel_scan`-shaped
    callable) switches the ladder to the WHEEL calendar: one bucket
    index built at entry, per-level origins/boundary read from it in
    O(buckets), and only each level's served clients re-slotted in
    place (see the wheel section comment -- every read is bit-equal
    to the dense reduction it replaces, so the committed stream is
    unchanged).  ``wstats`` is then ``(reslots, occ_hwm)`` int64
    scalars for the metrics plane, else None."""
    n = invariant["active"].shape[-1]
    acc0 = dict(units=jnp.zeros((n,), jnp.int32),
                served=jnp.zeros((n,), jnp.int32),
                served_resv=jnp.zeros((n,), jnp.int32),
                lb=jnp.zeros((n,), jnp.int32),
                cost=jnp.zeros((n,), jnp.int64),
                # newest boundary-distance margin per client across
                # levels (-1 = never observed): the flight record's
                # margin column for the whole bucketed batch
                margin=jnp.full((n,), jnp.int64(-1)))
    tacc0 = {}
    if with_hists:
        tacc0["h"] = obshist.hist_zero()
    if with_ledger:
        tacc0["l"] = obshist.ledger_zero(n)
    if with_slo:
        tacc0["s"] = obsslo.window_zero(n)
    if prov0 is not None:
        tacc0["p"] = prov0

    wheel0 = None
    if wheel_scan_fn is not None:
        wheel0 = wheel_build(EngineState(**invariant, **mut), now,
                             allow, scan_fn=wheel_scan_fn)

    def level(carry, _):
        if wheel_scan_fn is not None:
            mut, acc, tacc, w = carry
        else:
            mut, acc, tacc = carry
            w = None
        st = EngineState(**invariant, **mut)
        win = ring_window(st, steps, use_pallas=use_pallas)
        arr_rows, cost_rows = _heads_rows((win.arr, win.cost), steps)
        batch, b_eff, _ = _calendar_batch_core(
            st, now, arr_rows, cost_rows,
            anticipation_ns=anticipation_ns, allow_limit_break=allow,
            origins=None if w is None else wheel_origins(w),
            stop_min=None if w is None else functools.partial(
                _wheel_stop_min, scan_fn=wheel_scan_fn))
        if w is not None:
            # fixed-now commit: exactly the served clients moved
            w = wheel_adjust(w, batch.state, now, allow,
                             batch.served > 0)
        new_mut = {f: getattr(batch.state, f) for f in _EPOCH_MUTABLE}
        acc = dict(units=acc["units"] + batch.units,
                   served=acc["served"] + batch.served,
                   served_resv=acc["served_resv"] + batch.served_resv,
                   lb=acc["lb"] + batch.lb,
                   cost=acc["cost"] + batch.served_cost,
                   margin=jnp.where(batch.margin >= 0, batch.margin,
                                    acc["margin"]))
        if with_hists or with_ledger or with_slo or prov0 is not None:
            # per-LEVEL entry classification: level i starts from the
            # exact serial state at boundary i-1, so these are the
            # same observations L sequential minstop batches would
            # record
            cls_e, key_e = _classify(st, now, allow)
            hd, ld, sd = _telemetry_delta(
                batch.state, now, cls_e, key_e, batch.served,
                batch.served_resv, batch.lb, batch.count,
                with_hists, with_ledger,
                cost_pc=batch.served_cost, with_slo=with_slo)
            tacc = dict(tacc)
            if with_hists:
                tacc["h"] = obshist.hist_combine(tacc["h"], hd)
            if with_ledger:
                tacc["l"] = obshist.ledger_combine(tacc["l"], ld)
            if with_slo:
                tacc["s"] = obsslo.window_combine(tacc["s"], sd)
            if prov0 is not None:
                has_req = st.active & (st.depth > 0)
                elig = cls_e != CLS_NONE
                tacc["p"] = obsprov.prov_observe(
                    tacc["p"], now=now, elig=elig,
                    gated=has_req & ~elig,
                    win_cls=jnp.min(jnp.where(elig, cls_e, CLS_NONE)),
                    served_pc=batch.served, margins=batch.margin)
        # a level that commits nothing WITH candidates present is a
        # ladder stall: progress_ok's per-level analog (later levels
        # deterministically repeat it -- same state, same boundary)
        stall = ~batch.progress_ok
        out = (batch.count, batch.resv_count, b_eff, stall)
        if wheel_scan_fn is not None:
            return (new_mut, acc, tacc, w), out
        return (new_mut, acc, tacc), out

    if wheel_scan_fn is not None:
        (mut, acc, tacc, wfin), outs = lax.scan(
            level, (mut, acc0, tacc0, wheel0), None, length=levels)
        return mut, acc, tacc, outs, (wfin.reslots, wfin.hwm)
    (mut, acc, tacc), outs = lax.scan(level, (mut, acc0, tacc0), None,
                                      length=levels)
    return mut, acc, tacc, outs, None


def calendar_batch_bucketed(state: EngineState, now, *, steps: int,
                            levels: int,
                            anticipation_ns: int = 0,
                            allow_limit_break: bool = False,
                            use_pallas: bool | None = None
                            ) -> CalendarLadderBatch:
    """One bucketed calendar batch: L fused ladder levels (see section
    comment), each committing the exact serial prefix below its own
    refreshed stop-key boundary with a fresh per-client ``steps``
    budget.  With ``levels=1`` the committed set, the final state, and
    every counter are bit-identical to :func:`calendar_batch` (the
    ci.sh digest gate)."""
    assert steps <= state.ring_capacity, \
        "calendar steps exceed the ring window"
    assert levels >= 1, "the ladder needs at least one level"
    invariant = {f: getattr(state, f) for f in _EPOCH_INVARIANT}
    mut0 = {f: getattr(state, f) for f in _EPOCH_MUTABLE}
    mut, acc, _tacc, (count, resv, bound, stall), _w = \
        _calendar_ladder_scan(
            invariant, mut0, now, steps=steps, levels=levels,
            anticipation_ns=anticipation_ns, allow=allow_limit_break,
            use_pallas=use_pallas)
    total = jnp.sum(count).astype(jnp.int32)
    return CalendarLadderBatch(
        state=EngineState(**invariant, **mut),
        count=total,
        resv_count=jnp.sum(resv).astype(jnp.int32),
        units=acc["units"], served=acc["served"],
        served_resv=acc["served_resv"], lb=acc["lb"],
        progress_ok=~stall[0],
        level_count=count, level_bound=bound, level_stall=stall,
        served_cost=acc["cost"])


def calendar_batch_wheel(state: EngineState, now, *, steps: int,
                         levels: int, anticipation_ns: int = 0,
                         allow_limit_break: bool = False,
                         use_pallas: bool | None = None,
                         wheel_kernel: str = "xla"
                         ) -> CalendarLadderBatch:
    """One WHEEL calendar batch: the bucketed ladder driven by the
    maintained bucket index (wheel section comment) -- same
    :class:`CalendarLadderBatch` contract, bit-identical committed
    set/state/counters to :func:`calendar_batch_bucketed` at the same
    ``levels`` (and to :func:`calendar_batch` at ``levels=1``); the
    ci.sh wheel digest gates pin both."""
    assert steps <= state.ring_capacity, \
        "calendar steps exceed the ring window"
    assert levels >= 1, "the ladder needs at least one level"
    scan_fn = _wheel_resolve(wheel_kernel, state.capacity)
    invariant = {f: getattr(state, f) for f in _EPOCH_INVARIANT}
    mut0 = {f: getattr(state, f) for f in _EPOCH_MUTABLE}
    mut, acc, _tacc, (count, resv, bound, stall), _w = \
        _calendar_ladder_scan(
            invariant, mut0, now, steps=steps, levels=levels,
            anticipation_ns=anticipation_ns, allow=allow_limit_break,
            use_pallas=use_pallas, wheel_scan_fn=scan_fn)
    total = jnp.sum(count).astype(jnp.int32)
    return CalendarLadderBatch(
        state=EngineState(**invariant, **mut),
        count=total,
        resv_count=jnp.sum(resv).astype(jnp.int32),
        units=acc["units"], served=acc["served"],
        served_resv=acc["served_resv"], lb=acc["lb"],
        progress_ok=~stall[0],
        level_count=count, level_bound=bound, level_stall=stall,
        served_cost=acc["cost"])


def calendar_stop_ladder(state: EngineState, now, *, steps: int,
                         levels: int, anticipation_ns: int = 0,
                         allow_limit_break: bool = False,
                         heads=None):
    """The histogram PLANNER view of the ladder: one measure pass,
    then the stop-key CDF quantiles B_1 <= ... <= B_levels via the
    shared dense-histogram rounds (kernels.radix_quantile_ladder).
    B_1 is exactly the minstop boundary; the higher quantiles predict
    where successive refreshed-budget commit levels land on a skewed
    stop distribution (diagnostic/sizing -- the commit path itself
    re-measures per level; see section comment).

    Returns ``(ladder int64[levels], stop_pk int64[N])``."""
    assert steps <= state.ring_capacity, \
        "calendar steps exceed the ring window"
    if heads is None:
        win = ring_window(state, steps)
        heads = (win.arr, win.cost)
    arr_rows, cost_rows = _heads_rows(heads, steps)
    cls0, key0 = _classify(state, now, allow_limit_break)
    kresv = jnp.min(jnp.where(cls0 == CLS_RESV, key0, KEY_INF))
    kprop1 = jnp.min(jnp.where(cls0 == CLS_WEIGHT, key0, KEY_INF))
    kprop2 = jnp.min(jnp.where(cls0 == CLS_LB, key0, KEY_INF))
    stop_pk = _calendar_pass(state, now, arr_rows, cost_rows,
                             allow_limit_break, anticipation_ns,
                             kresv, kprop1, kprop2, None)
    return kernels.radix_quantile_ladder(stop_pk, levels), stop_pk


class CalendarEpoch(NamedTuple):
    """M calendar batches' output, compact for one readback."""

    state: EngineState
    count: jnp.ndarray        # int32[M] decisions per batch
    resv_count: jnp.ndarray   # int32[M]
    progress_ok: jnp.ndarray  # bool[M]
    served: jnp.ndarray       # int32[N] per-client decisions (whole
    #                           epoch; calibration feed)
    metrics: jnp.ndarray      # int64[NUM_METRICS] (zeros unless
    #                           with_metrics)
    level_count: jnp.ndarray  # int32[M, L] decisions per ladder level
    #                           (L = ladder_levels for "bucketed", 1
    #                           for "minstop"; bench decisions-per-
    #                           level attribution)
    # telemetry plane (None unless the caller passed an accumulator)
    hists: object = None
    ledger: object = None
    flight: object = None
    slo: object = None
    prov: object = None


def scan_calendar_epoch(state: EngineState, now, m: int, *,
                        steps: int, anticipation_ns: int = 0,
                        allow_limit_break: bool = False,
                        use_pallas: bool | None = None,
                        with_metrics: bool = False,
                        tag_width: int = 64,
                        calendar_impl: str = "minstop",
                        ladder_levels: int = 8,
                        wheel_kernel: str = "xla",
                        hists=None, ledger=None,
                        flight=None, slo=None,
                        prov=None) -> CalendarEpoch:
    """Run m calendar batches on device (each prefetches its own
    ``steps``-row ring window).  ``tag_width`` as in
    :func:`scan_prefix_epoch` (a window trip reports
    ``progress_ok=False`` for that batch and every later one).

    ``calendar_impl`` (STATIC, "minstop"|"bucketed"|"wheel") picks the
    commit boundary scheme, mirroring the prefix engine's
    ``select_impl`` switch: "minstop" is one global min-stop boundary
    per batch; "bucketed" fuses ``ladder_levels`` refreshed-budget
    boundaries per batch (see the bucketed section comment), so one
    launch commits what took ``ladder_levels`` minstop batches;
    "wheel" is the bucketed ladder driven by the maintained bucket
    index (wheel section comment) with its boundary scan behind the
    ``wheel_kernel`` switch ("xla" reference or the "pallas" kernel
    with a counted fallback).  All produce exact serial prefixes;
    ``ladder_levels=1`` is bit-identical to "minstop" (ci.sh digest
    gates).

    ``hists`` / ``ledger`` / ``flight`` telemetry accumulators as in
    :func:`scan_prefix_epoch`.  Histogram/ledger observations are per
    LEVEL (a bucketed ladder level == one minstop batch, so bucketed-L
    telemetry equals the L-batch minstop composition exactly); flight
    records are per CLIENT per BATCH (the calendar engine emits
    per-client counts, not an ordered stream), the cost column
    carrying the client's committed decisions."""
    assert tag_width in (32, 64), tag_width
    assert calendar_impl in _CAL_IMPLS, calendar_impl
    wheel = calendar_impl == "wheel"
    bucketed = calendar_impl == "bucketed" or wheel
    levels = int(ladder_levels) if bucketed else 1
    assert levels >= 1, "the ladder needs at least one level"
    if wheel:
        wheel_fn = _wheel_resolve(wheel_kernel, state.capacity)
    else:
        wheel_fn = None
    narrow32 = tag_width == 32
    invariant = {f: getattr(state, f) for f in _EPOCH_INVARIANT}
    mutable0_64 = {f: getattr(state, f) for f in _EPOCH_MUTABLE}
    served0 = jnp.zeros((state.capacity,), dtype=jnp.int32)
    met0 = obsdev.metrics_zero()
    tele0 = _tele_init(state, hists, ledger, flight, slo, prov)
    need_tele = bool(tele0)
    if narrow32:
        tc = _TagCarry32(state)
        mutable0, ok0 = tc.narrow(mutable0_64)
        if with_metrics:
            met0 = obsdev.metrics_combine(met0, obsdev.metrics_delta(
                rebase_fallbacks=(~ok0).astype(jnp.int64)))
        carry0 = (mutable0, served0, met0, tele0, ~ok0)
    else:
        carry0 = (mutable0_64, served0, met0, tele0)

    def body(carry, _):
        if narrow32:
            mut, acc, met, tele, dead = carry
            st = EngineState(**invariant, **tc.widen(mut))
        else:
            mut, acc, met, tele = carry
            st = EngineState(**invariant, **mut)
        hd = ld = sd = p_new = margin_pc = None
        if need_tele:
            # batch-entry classification, shared by the minstop
            # telemetry delta and the flight records (ONE definition,
            # so the two cannot drift); the bucketed ladder computes
            # its own per-LEVEL classification internally, and XLA
            # drops this one when nothing reads it
            cls_e, key_e = _classify(st, now, allow_limit_break)
        w_reslots = jnp.int64(0)
        w_hwm = jnp.int64(0)
        if bucketed:
            mut_in = {f: getattr(st, f) for f in _EPOCH_MUTABLE}
            new_mut, lacc, tdelta, \
                (lvl_count, lvl_resv, _bound, lvl_stall), wstats = \
                _calendar_ladder_scan(
                    invariant, mut_in, now, steps=steps,
                    levels=levels, anticipation_ns=anticipation_ns,
                    allow=allow_limit_break, use_pallas=use_pallas,
                    with_hists="h" in tele, with_ledger="l" in tele,
                    with_slo="s" in tele, prov0=tele.get("p"),
                    wheel_scan_fn=wheel_fn)
            if wstats is not None:
                w_reslots, w_hwm = wstats
            hd, ld, sd = (tdelta.get("h"), tdelta.get("l"),
                          tdelta.get("s"))
            p_new = tdelta.get("p")
            margin_pc = lacc["margin"]
            batch_state = EngineState(**invariant, **new_mut)
            count = jnp.sum(lvl_count).astype(jnp.int32)
            resv_count = jnp.sum(lvl_resv).astype(jnp.int32)
            progress = ~lvl_stall[0]
            served = lacc["served"]
            lb_total = jnp.sum(lacc["lb"]).astype(jnp.int64)
            levels_used = jnp.sum((lvl_count > 0)
                                  .astype(jnp.int64))
            ladder_fb = jnp.any(lvl_stall).astype(jnp.int64)
            base_decs = lvl_count[0].astype(jnp.int64)
        else:
            win = ring_window(st, steps, use_pallas=use_pallas)
            batch = calendar_batch(
                st, now, steps=steps,
                anticipation_ns=anticipation_ns,
                allow_limit_break=allow_limit_break,
                heads=(win.arr, win.cost))
            batch_state = batch.state
            count, resv_count = batch.count, batch.resv_count
            progress = batch.progress_ok
            served = batch.served
            lb_total = jnp.sum(batch.lb).astype(jnp.int64)
            lvl_count = count[None]
            levels_used = (count > 0).astype(jnp.int64)
            ladder_fb = jnp.int64(0)
            base_decs = count.astype(jnp.int64)
            new_mut = {f: getattr(batch.state, f)
                       for f in _EPOCH_MUTABLE}
            margin_pc = batch.margin
            if "h" in tele or "l" in tele or "s" in tele:
                hd, ld, sd = _telemetry_delta(
                    batch.state, now, cls_e, key_e, batch.served,
                    batch.served_resv, batch.lb, batch.count,
                    "h" in tele, "l" in tele,
                    cost_pc=batch.served_cost, with_slo="s" in tele)
            if "p" in tele:
                has_req = st.active & (st.depth > 0)
                elig = cls_e != CLS_NONE
                p_new = obsprov.prov_observe(
                    tele["p"], now=now, elig=elig,
                    gated=has_req & ~elig,
                    win_cls=jnp.min(jnp.where(elig, cls_e,
                                              CLS_NONE)),
                    served_pc=batch.served, margins=batch.margin)
        trip = jnp.bool_(False)
        good = jnp.bool_(True)
        if narrow32:
            mut, dead, good, trip, \
                (count, resv_count, progress, served, lb_total,
                 lvl_count, levels_used, ladder_fb,
                 base_decs, w_reslots, w_hwm) = tc.gate(
                    dead, mut, new_mut,
                    [(count, 0), (resv_count, 0), (progress, False),
                     (served, 0), (lb_total, 0),
                     (lvl_count, jnp.zeros((levels,), jnp.int32)),
                     (levels_used, 0), (ladder_fb, 0),
                     (base_decs, 0), (w_reslots, 0), (w_hwm, 0)])
        else:
            mut = new_mut
        out = (count, resv_count, progress, lvl_count)
        if with_metrics:
            met = _batch_metrics(
                met, batch_state, count=count,
                resv=resv_count,
                prop=count - resv_count,
                lb=lb_total,
                # a calendar batch with candidates that cannot make
                # progress is the guard-trip analog (serial fallback)
                guards_ok=progress | ~good, rebase_fallback=trip,
                live=good,
                ladder_levels_used=levels_used,
                ladder_base_decisions=base_decs,
                ladder_fallbacks=ladder_fb,
                wheel_occ_hwm=w_hwm, wheel_reslots=w_reslots)
        if need_tele:
            tele = _tele_fold(tele, hd, ld, good, sd)
            if "p" in tele:
                tele["p"] = obsprov.prov_select(good, p_new,
                                                tele["p"])
            if "f" in tele:
                # per-client-per-batch records (the calendar engine
                # emits counts, not a stream); GATED served, so a
                # dead batch records nothing
                has_req = st.active & (st.depth > 0)
                gate_n = jnp.sum(has_req & (cls_e == CLS_NONE)) \
                    .astype(jnp.int64)
                iota = jnp.arange(st.capacity, dtype=jnp.int32)
                tele = _tele_flight(
                    tele, jnp.where(served > 0, iota, -1),
                    cls_e.astype(jnp.int64), key_e,
                    served.astype(jnp.int64), good,
                    margin=margin_pc, gate=gate_n)
        carry = (mut, acc + served, met, tele, dead) if narrow32 \
            else (mut, acc + served, met, tele)
        return carry, out

    carry, (count, resv, ok, lvls) = lax.scan(body, carry0, None,
                                              length=m)
    mutable, served, metrics = carry[0], carry[1], carry[2]
    tele = carry[3]
    if narrow32:
        state = EngineState(**invariant,
                            **tc.restore(mutable, mutable0_64, ok0))
    else:
        state = EngineState(**invariant, **mutable)
    return CalendarEpoch(state=state, count=count, resv_count=resv,
                         progress_ok=ok, served=served,
                         metrics=metrics, level_count=lvls,
                         hists=tele.get("h"), ledger=tele.get("l"),
                         flight=tele.get("f"), slo=tele.get("s"),
                         prov=tele.get("p"))


# ----------------------------------------------------------------------
# epoch-engine dispatch: the one registry + kwargs normalization
# ----------------------------------------------------------------------
#
# Every epoch body doubles as a STREAM STEP: the guarded runner
# (robust.guarded), the streaming chunk program (engine.stream), and
# any future caller must resolve "engine name -> scan fn + the kwargs
# that engine actually takes" IDENTICALLY, or a knob silently applied
# to one loop and not the other would break the stream-vs-round
# digest gate.  One implementation here; callers never hand-build the
# kwarg dicts.

EPOCH_ENGINES = ("prefix", "chain", "calendar")

# Decision-stream field classification for the lifecycle plane's
# canonical client-id-space digest (lifecycle.plane.canon_results):
# SLOT fields hold client slot indices (-1 pads) that must translate
# through the slot map; CAPACITY fields are per-slot arrays over the
# full [capacity] axis that must scatter to client-id space.  Every
# other digest field is layout-invariant already -- the engines'
# selection reductions are permutation-invariant over slots (mins /
# sums / any) and their sorts tie-break on the per-client creation
# ``order``, which moves with its row.
DECISION_SLOT_FIELDS = {"prefix": ("slot",), "chain": ("slot",),
                        "calendar": ()}
DECISION_CAPACITY_FIELDS = {"prefix": (), "chain": (),
                            "calendar": ("served",)}


def epoch_scan_fn(engine: str):
    """The epoch-scan callable for ``engine`` (raises KeyError on an
    unknown name)."""
    return {"prefix": scan_prefix_epoch, "chain": scan_chain_epoch,
            "calendar": scan_calendar_epoch}[engine]


def epoch_scan_kwargs(engine: str, *, k: int = 0, chain_depth: int = 4,
                      select_impl: str = "sort", tag_width: int = 64,
                      window_m: int | None = None,
                      calendar_impl: str = "minstop",
                      ladder_levels: int = 8,
                      wheel_kernel: str = "xla",
                      anticipation_ns: int = 0,
                      allow_limit_break: bool = False,
                      with_metrics: bool = False) -> dict:
    """Normalize the shared knob set into the kwargs ``engine``'s scan
    accepts: prefix reads k/select_impl/window_m, chain reads
    k/select_impl/chain_depth, and the calendar engine has no [k] cap
    -- k doubles as its per-client serve-step budget (``steps``)."""
    if engine not in EPOCH_ENGINES:
        raise ValueError(f"unknown epoch engine {engine!r} "
                         f"(one of {EPOCH_ENGINES})")
    kw = dict(anticipation_ns=anticipation_ns,
              allow_limit_break=allow_limit_break,
              with_metrics=with_metrics, tag_width=tag_width)
    if engine == "prefix":
        kw.update(k=k, select_impl=select_impl, window_m=window_m)
    elif engine == "chain":
        kw.update(k=k, select_impl=select_impl,
                  chain_depth=chain_depth)
    else:
        kw.update(steps=max(k, 1), calendar_impl=calendar_impl,
                  ladder_levels=ladder_levels,
                  wheel_kernel=wheel_kernel)
    return kw
