"""The plain dmClock reference that decides ``correct``.

A straightforward re-statement of the dmClock pull scheduler
(Gulati et al., OSDI 2010; upstream ceph/dmclock ``dmclock_server.h``
do_add_request / do_next_request / pop_process_request /
update_next_tag / reduce_reservation_tags) in plain ``jax.numpy``,
vectorised over clients only.  It imports nothing of the program and
takes nothing the program made except the answers it judges: the
per-epoch decisions.

What it holds the program to, per epoch (one epoch = one arrival
superwave at ``t_base`` and one serving pass at the fixed time
``now = t_base + epoch``):

* admission: each client takes ``min(raw, ring - depth, waves)`` of
  its raw arrivals, at wave times ``t_base + w * epoch / waves``;
* tags: the dmClock recurrence ``tag = max(arrival, prev + inv *
  (dist + cost))`` per axis (sentinels for a disabled axis), delayed
  tagging of a new queue head, and the reservation-debt reduction
  after a weight-phase serve;
* work conservation: a server pulls whenever a request can be served
  and it has capacity, so an epoch that decided fewer requests than
  the configured capacity x epoch must leave nothing servable queued;
* selection: at fixed ``now`` the serial scheduler always serves the
  least (class, key, client order) head, class 0 = reservation tag
  due, 1 = ready by weight, 2 = limit break (AtLimit::Allow), and a
  client's own sequence is fixed by its own tags.  So the decisions
  of an epoch are a prefix of the serial order exactly when, with each
  client's sequence of keys replaced by its running maximum, every
  served (key, order, j) lies below every first-unserved one.  For an
  engine that reports the ordered stream, the stream must also ascend
  in that order, and each decision's phase (reservation or weight)
  and limit-break flag must match its class.

Every epoch of the run is checked, from the initial state; the
reference keeps its own state and advances it by the decisions it
has just accepted.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

MAX_TAG = 1 << 62
MIN_TAG = -(1 << 62)
MAX_CHARGE_UNITS = 1 << 20
ORGANIC_TAG_CAP = MAX_TAG - 1
I64_MIN = -(1 << 63)
I64_MAX = (1 << 63) - 1
NONE = 3             # class of a head that cannot be served at now


class Ref(NamedTuple):
    r_inv: jnp.ndarray
    w_inv: jnp.ndarray
    l_inv: jnp.ndarray
    order: jnp.ndarray
    prev_r: jnp.ndarray
    prev_p: jnp.ndarray
    prev_l: jnp.ndarray
    head_r: jnp.ndarray
    head_p: jnp.ndarray
    head_l: jnp.ndarray
    depth: jnp.ndarray        # requests queued, head included
    tail: jnp.ndarray         # [N, Q] arrival times behind the head,
    #                           oldest first (column 0)


def tag(t, prev, inv, dist, cost, high: bool):
    """One tag axis: max(arrival, prev + inv * (dist + cost)), capped;
    a disabled axis (inv 0) pins to +inf (reservation, weight) or
    -inf (limit)."""
    units = jnp.minimum(dist + cost, MAX_CHARGE_UNITS)
    organic = jnp.minimum(jnp.maximum(t, prev + inv * units),
                          ORGANIC_TAG_CAP)
    return jnp.where(inv == 0, MAX_TAG if high else MIN_TAG, organic)


def fold(prev, t):
    """prev tag update: a pinned sentinel leaves prev as it was."""
    return jnp.where((t == MAX_TAG) | (t == MIN_TAG), prev, t)


def initial(con: dict, pre: dict, ring: int) -> Ref:
    n = con["resv_inv"].shape[0]
    hr, hp, hl = (jnp.asarray(pre[k], jnp.int64)
                  for k in ("head_resv", "head_prop", "head_limit"))
    return Ref(r_inv=jnp.asarray(con["resv_inv"], jnp.int64),
               w_inv=jnp.asarray(con["weight_inv"], jnp.int64),
               l_inv=jnp.asarray(con["limit_inv"], jnp.int64),
               order=jnp.arange(n, dtype=jnp.int64),
               prev_r=fold(jnp.zeros_like(hr), hr),
               prev_p=fold(jnp.zeros_like(hp), hp),
               prev_l=fold(jnp.zeros_like(hl), hl),
               head_r=hr, head_p=hp, head_l=hl,
               depth=jnp.full((n,), pre["depth"], jnp.int32),
               tail=jnp.zeros((n, ring), jnp.int64))


def _lex_less(a, b):
    """a < b for tuples of equal-shaped arrays, first element first."""
    less = jnp.zeros(jnp.shape(a[0]), bool)
    equal = jnp.ones(jnp.shape(a[0]), bool)
    for x, y in zip(a, b):
        less = less | (equal & (x < y))
        equal = equal & (x == y)
    return less


def _lex_pick(mask, cols, largest: bool):
    """The lexicographically largest (or least) masked row of ``cols``
    ([N] arrays), as a tuple of scalars; (found, tuple)."""
    out = []
    m = mask
    for c in cols:
        fill = I64_MIN if largest else I64_MAX
        v = jnp.where(m, c.astype(jnp.int64), fill)
        best = jnp.max(v) if largest else jnp.min(v)
        out.append(best)
        m = m & (c.astype(jnp.int64) == best)
    return jnp.any(mask), tuple(out)


def _shift_left(x, s):
    """Row i of ``x`` moved left by s[i] (0 <= s <= Q) columns."""
    q = x.shape[1]
    b = 0
    while (1 << b) <= q:
        bit = ((s >> b) & 1).astype(bool)
        x = jnp.where(bit[:, None], jnp.roll(x, -(1 << b), axis=1), x)
        b += 1
    return x


@partial(jax.jit, static_argnames=("waves", "epoch_ns", "rho", "delta",
                                   "allow", "keep_keys"))
def epoch(ref: Ref, raw, served, t_base, cost, *, waves: int,
          epoch_ns: int, rho: int, delta: int, allow: bool,
          keep_keys: bool = False):
    """Ingest one epoch's raw arrivals, judge ``served`` (int[N], the
    program's decisions per client at now = t_base + epoch_ns), and
    advance by them.  ``cost`` (int64[N]) is each client's request
    cost, the same for every request of a client.  Returns (ref',
    verdict, keys) where verdict is int64[5] = [admitted, bad_serves,
    prefix_violation, decisions, eligible_left] (eligible_left: a
    request that could be served at now is still queued after the
    epoch) and keys (keep_keys only) = [N, Q] running-max class / key /
    own class of each served position, for the ordered-stream check."""
    n, q = ref.tail.shape
    t_base = jnp.asarray(t_base, jnp.int64)
    now = t_base + epoch_ns
    dt_wave = epoch_ns // waves

    # ---- admission and arrival (do_add_request, delayed tagging)
    c = jnp.minimum(raw.astype(jnp.int32),
                    jnp.minimum(q - ref.depth, waves))
    new_head = (c > 0) & (ref.depth == 0)
    nr = tag(t_base, ref.prev_r, ref.r_inv, rho, cost, True)
    np_ = tag(t_base, ref.prev_p, ref.w_inv, delta, cost, True)
    nl = tag(t_base, ref.prev_l, ref.l_inv, delta, cost, False)
    head_r = jnp.where(new_head, nr, ref.head_r)
    head_p = jnp.where(new_head, np_, ref.head_p)
    head_l = jnp.where(new_head, nl, ref.head_l)
    prev_r = jnp.where(new_head, fold(ref.prev_r, nr), ref.prev_r)
    prev_p = jnp.where(new_head, fold(ref.prev_p, np_), ref.prev_p)
    prev_l = jnp.where(new_head, fold(ref.prev_l, nl), ref.prev_l)
    # the rest queue behind the head: arrival w of this epoch at
    # t_base + w * dt_wave, appended after the current tail
    h = new_head.astype(jnp.int32)
    tail_len = jnp.maximum(ref.depth - 1, 0)
    col = jnp.arange(q, dtype=jnp.int32)[None, :]
    pos = col - tail_len[:, None]
    put = (pos >= 0) & (pos < (c - h)[:, None])
    tail = jnp.where(put, t_base + (pos + h[:, None]).astype(jnp.int64)
                     * dt_wave, ref.tail)
    depth = ref.depth + c
    head_arr = jnp.zeros((n,), jnp.int64)   # only the tag matters below

    # ---- judge and serve: client i serves served[i] times
    served = served.astype(jnp.int32)
    bad0 = jnp.sum(served > depth)
    jmax = jnp.max(served)
    # column j holds the j-th position of each client; one spare
    # column takes the first-unserved position of a client that
    # served all Q
    keys0 = jnp.zeros((3, n, q + 1) if keep_keys else (3, 1, 1),
                      jnp.int64)

    def body(carry):
        (j, hr, hp, hl, ha, pr, pp, pl, dep, ec, ek, bad,
         smax, umin, keys) = carry
        has = dep > 0
        cls = jnp.where(
            ~has, NONE,
            jnp.where(hr <= now, 0,
                      jnp.where((hl <= now) & (hp < MAX_TAG), 1,
                                jnp.where(allow & (hp < MAX_TAG), 2,
                                          NONE))))
        key = jnp.where(cls == 0, hr, hp)
        key = jnp.where(cls == NONE, 0, key)
        # running maximum of (class, key) along the client's sequence
        up = _lex_less((ec, ek), (cls, key))
        ec = jnp.where(up, cls, ec)
        ek = jnp.where(up, key, ek)
        jj = jnp.full((n,), j, jnp.int64)
        srv = j < served
        fst = (j == served) & (ec != NONE)
        bad = bad + jnp.sum(srv & (cls == NONE))
        f1, m1 = _lex_pick(srv, (ec, ek, ref.order, jj), True)
        smax = tuple(jnp.where(f1 & _lex_less(smax, m1), b, a)
                     for a, b in zip(smax, m1))
        f2, m2 = _lex_pick(fst, (ec, ek, ref.order, jj), False)
        umin = tuple(jnp.where(f2 & _lex_less(m2, umin), b, a)
                     for a, b in zip(umin, m2))
        if keep_keys:
            keys = lax.dynamic_update_slice(
                keys, jnp.stack([ec.astype(jnp.int64), ek,
                                 cls.astype(jnp.int64)])[:, :, None],
                (jnp.int32(0), jnp.int32(0), j))
        # serve the head (pop_process_request + update_next_tag +
        # reduce_reservation_tags) where this client serves now
        go = srv & (cls != NONE)
        more = dep > 1
        narr = lax.dynamic_slice_in_dim(tail, j, 1, axis=1)[:, 0]
        tr = tag(narr, hr, ref.r_inv, rho, cost, True)
        tp = tag(narr, hp, ref.w_inv, delta, cost, True)
        tl = tag(narr, hl, ref.l_inv, delta, cost, False)
        debt = jnp.where(cls >= 1, ref.r_inv * (cost + rho), 0)
        npr = jnp.where(more, fold(pr, tr), pr) - debt
        npp = jnp.where(more, fold(pp, tp), pp)
        npl = jnp.where(more, fold(pl, tl), pl)
        pick = go & more
        hr = jnp.where(pick, tr - debt, hr)
        hp = jnp.where(pick, tp, hp)
        hl = jnp.where(pick, tl, hl)
        ha = jnp.where(pick, narr, ha)
        pr = jnp.where(go, npr, pr)
        pp = jnp.where(go, npp, pp)
        pl = jnp.where(go, npl, pl)
        dep = jnp.where(go, dep - 1, dep)
        return (j + 1, hr, hp, hl, ha, pr, pp, pl, dep, ec, ek, bad,
                smax, umin, keys)

    lo = (jnp.int64(I64_MIN),) * 4
    hi = (jnp.int64(I64_MAX),) * 4
    carry = (jnp.int32(0), head_r, head_p, head_l, head_arr, prev_r,
             prev_p, prev_l, depth, jnp.full((n,), -1, jnp.int32),
             jnp.full((n,), I64_MIN, jnp.int64), bad0, lo, hi, keys0)
    carry = lax.while_loop(lambda c_: c_[0] <= jmax, body, carry)
    (_, hr, hp, hl, _, pr, pp, pl, dep, _, _, bad, smax, umin,
     keys) = carry
    violation = _lex_less(umin, smax).astype(jnp.int64)
    left = (umin[0] != I64_MAX).astype(jnp.int64)
    nxt = Ref(r_inv=ref.r_inv, w_inv=ref.w_inv, l_inv=ref.l_inv,
              order=ref.order, prev_r=pr, prev_p=pp, prev_l=pl,
              head_r=hr, head_p=hp, head_l=hl, depth=dep,
              tail=_shift_left(tail, jnp.minimum(served, q)))
    verdict = jnp.stack([jnp.sum(c).astype(jnp.int64),
                         bad.astype(jnp.int64), violation,
                         jnp.sum(served).astype(jnp.int64), left])
    return nxt, verdict, keys


@jax.jit
def stream_check(keys, order, slots, phase, lb, length):
    """The ordered stream of one epoch (padded to a fixed size, the
    first ``length`` entries real) against the reference's keys:
    returns [out_of_order, phase_mismatch, lb_mismatch]."""
    t = slots.shape[0]
    idx = jnp.arange(t)
    valid = idx < length
    big = jnp.int32(order.shape[0])        # pads sort after every slot
    s = jnp.where(valid, slots, big)
    srt = jnp.argsort(s, stable=True)
    ss = s[srt]
    first = jnp.searchsorted(ss, ss, side="left")
    occ = jnp.zeros((t,), jnp.int32).at[srt].set(
        (idx - first).astype(jnp.int32))
    sl = jnp.where(valid, slots, 0)
    oc = jnp.where(valid, occ, 0)
    ec = keys[0, sl, oc]
    ek = keys[1, sl, oc]
    cls = keys[2, sl, oc]
    o = order[sl]
    a = (ec[:-1], ek[:-1], o[:-1], oc[:-1].astype(jnp.int64))
    b = (ec[1:], ek[1:], o[1:], oc[1:].astype(jnp.int64))
    pair = valid[1:]
    out_of_order = jnp.sum(pair & ~_lex_less(a, b))
    phase_bad = jnp.sum(valid & (phase.astype(jnp.int64) != (cls != 0)))
    lb_bad = jnp.sum(valid & (lb != (cls == 2)))
    return jnp.stack([out_of_order, phase_bad, lb_bad])
