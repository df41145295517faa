#!/usr/bin/env python
"""Component profile of the prefix-commit engine (the tool behind
PROFILE.md).

Timing protocol: per-call dispatch overhead varies, so every
measurement here runs the component M_HI and M_LO times inside one
jitted ``lax.scan`` (data dependence threaded through the carry) and
reports ``(T(M_HI) - T(M_LO)) / (M_HI - M_LO)`` -- fixed per-call costs
cancel exactly.  All buffers are passed as real jit arguments: device
arrays captured as jit constants would be re-uploaded per call.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from __graft_entry__ import _preloaded_state
from dmclock_tpu.engine import fastpath
from profile_util import scalar_latency, state_digest

N = 100_000
K = 49152
M_LO, M_HI = 8, 32


def _time_call(f, *args, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f(*args)
        jax.device_get(state_digest(out.state) if hasattr(out, "state")
                       else out[1])
        best = min(best, time.perf_counter() - t0)
    return best


def measure_epoch(name, state, m_lo=M_LO, m_hi=M_HI, k=K, **ep_kw):
    """``ep_kw`` forwards to ``scan_prefix_epoch`` -- the
    ``select_impl`` / ``tag_width`` / ``window_m`` A/B rows below
    differ only here, so every variant shares one timing protocol."""
    f_lo = jax.jit(functools.partial(fastpath.scan_prefix_epoch,
                                     m=m_lo, k=k, anticipation_ns=0,
                                     **ep_kw))
    f_hi = jax.jit(functools.partial(fastpath.scan_prefix_epoch,
                                     m=m_hi, k=k, anticipation_ns=0,
                                     **ep_kw))
    now = jnp.int64(0)
    jax.device_get(state_digest(f_lo(state, now).state))
    jax.device_get(state_digest(f_hi(state, now).state))
    t_lo = _time_call(f_lo, state, now)
    t_hi = _time_call(f_hi, state, now)
    t = (t_hi - t_lo) / (m_hi - m_lo)
    print(f"{name:52s} {t*1e6:9.1f} us/batch  "
          f"({t/k*1e9:5.1f} ns/dec, {k/t/1e6:5.1f} M dec/s)")
    return t


def measure_scan(name, make_body, state, init):
    """make_body(state) -> (carry, _) -> carry scan body; differenced."""
    def mk(m):
        def fn(state, tick):
            body = make_body(state)
            c, vs = lax.scan(body, (tick, init), None, length=m)
            return state, c[0] + jnp.asarray(vs[0]).astype(jnp.int64).sum()
        return fn
    f_lo = jax.jit(mk(64))
    f_hi = jax.jit(mk(256))
    jax.device_get(f_lo(state, jnp.int64(0))[1])
    jax.device_get(f_hi(state, jnp.int64(0))[1])
    t_lo = _time_call(f_lo, state, jnp.int64(0))
    t_hi = _time_call(f_hi, state, jnp.int64(0))
    t = (t_hi - t_lo) / (256 - 64)
    print(f"{name:52s} {t*1e6:9.1f} us/iter")
    return t


def _zipf_state(n, ring, depth):
    """cfg4-like Zipf-64 skew over the preload: the calendar A/B's
    honest shape (uniform weights give minstop nothing to lose -- the
    min-stop IS everyone's stop; the ladder's gain is the skew)."""
    from dmclock_tpu.core.timebase import rate_to_inv_ns

    st = _preloaded_state(n, depth, ring=ring)
    w = 1.0 / np.arange(1, n + 1) ** 1.1
    w = np.clip(w / w[n // 2], 0.5, 64.0)
    rng = np.random.default_rng(7)
    rng.shuffle(w)
    winv = np.asarray([rate_to_inv_ns(x) for x in w], np.int64)
    c = np.arange(n)
    phase = ((c * 2654435761) & 0xFFFFF) / float(1 << 20)
    jitter = (phase * 2.0 * winv).astype(np.int64)
    return st._replace(weight_inv=jnp.asarray(winv),
                       head_prop=jnp.asarray(winv + jitter))


def measure_calendar(name, state, *, impl, levels, m_lo=4, m_hi=12,
                     steps=8, **cal_kw):
    """Calendar-epoch A/B row (minstop vs bucketed ladder vs wheel):
    marginal batch cost AND marginal decisions -- the impls commit
    different amounts per batch, so dec/s is the honest comparison,
    not us/batch alone.  ``cal_kw`` forwards to
    ``scan_calendar_epoch`` (the wheel_kernel xla/pallas A/B differs
    only there)."""
    mk = lambda m: jax.jit(functools.partial(       # noqa: E731
        fastpath.scan_calendar_epoch, m=m, steps=steps,
        anticipation_ns=0, calendar_impl=impl, ladder_levels=levels,
        **cal_kw))
    f_lo, f_hi = mk(m_lo), mk(m_hi)
    now = jnp.int64(0)
    jax.device_get(state_digest(f_lo(state, now).state))
    ep_hi = f_hi(state, now)
    jax.device_get(state_digest(ep_hi.state))
    t_lo = _time_call(f_lo, state, now)
    t_hi = _time_call(f_hi, state, now)
    t = (t_hi - t_lo) / (m_hi - m_lo)
    counts = np.asarray(jax.device_get(ep_hi.count))
    d = counts[m_lo:].sum() / (m_hi - m_lo)   # marginal batches only
    print(f"{name:52s} {t*1e6:9.1f} us/batch  "
          f"({d:7.0f} dec/batch, {d/max(t, 1e-12)/1e6:5.1f} M dec/s)")
    return t, d


def _high_rate_state(n, ring):
    """_preloaded_state with client rates x1000 (weights 1000..4000/s):
    per-serve tag advance ~1e6 ns, so a whole epoch's virtual-time
    drift fits the int32 rebase window and tag_width=32 never trips --
    the shape the rebase measurement is honest on (the default 1..4/s
    preload drifts ~1e9 ns/serve and falls back within one batch,
    which would measure the fallback, not the carry)."""
    st = _preloaded_state(n, 128, ring=ring)
    return st._replace(
        resv_inv=st.resv_inv // 1000,
        weight_inv=st.weight_inv // 1000,
        head_resv=st.head_resv // 1000,
        head_prop=st.head_prop // 1000)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=N,
                    help="clients (smaller for cpu-box checks)")
    ap.add_argument("--k", type=int, default=K)
    args = ap.parse_args()
    n, k = args.n, args.k

    print(f"scalar round-trip latency: {scalar_latency()*1e3:.1f} ms\n")
    state = _preloaded_state(n, 128, ring=128)

    # -- whole epoch at bench shape, under both selection backends and
    # (on the window-fitting high-rate shape) both tag widths
    measure_epoch(f"scan_prefix_epoch (k={k}, ring=128)", state, k=k)
    measure_epoch(f"scan_prefix_epoch radix (k={k})", state, k=k,
                  select_impl="radix")
    hi = _high_rate_state(n, 128)
    measure_epoch(f"scan_prefix_epoch tag64 (high-rate, k={k})", hi,
                  k=k)
    measure_epoch(f"scan_prefix_epoch tag32 (high-rate, k={k})", hi,
                  k=k, tag_width=32)
    measure_epoch(f"scan_prefix_epoch m=64 window_m=8 (k={k})", state,
                  m_lo=16, m_hi=64, k=k, window_m=8)

    # -- calendar engine: minstop vs the bucketed stop-key ladder on a
    # Zipf-64-skewed backlog (the cfg4 cutter shape; docs/ENGINE.md).
    # The ladder fuses L measure+commit boundaries per launch, so its
    # batch costs ~L x more and must commit ~L x more to win -- the
    # dec/s column is the verdict.
    zs = _zipf_state(n, 128, 96)
    measure_calendar("scan_calendar_epoch minstop (steps=8)", zs,
                     impl="minstop", levels=1)
    measure_calendar("scan_calendar_epoch bucketed L=4 (steps=8)", zs,
                     impl="bucketed", levels=4)
    measure_calendar("scan_calendar_epoch bucketed L=8 (steps=8)", zs,
                     impl="bucketed", levels=8)
    # -- wheel: same ladder driven from the maintained bucket index
    # (O(1)-bucket re-slot per commit instead of an O(N) rebuild per
    # boundary), then the bucket kernel itself A/B'd xla vs pallas
    # (pallas raises off TPU without DMCLOCK_WHEEL_INTERPRET=1).
    measure_calendar("scan_calendar_epoch wheel L=8 (steps=8)", zs,
                     impl="wheel", levels=8)
    measure_calendar(
        "scan_calendar_epoch wheel L=8 kernel=pallas", zs,
        impl="wheel", levels=8, wheel_kernel="pallas")

    # -- selection core of _prefix_select: the 5-array 2-key i32 sort
    # plus the cumulative-min prefix validation
    def sel_sort(state):
        iota = jnp.arange(n, dtype=jnp.int32)
        o32 = state.order.astype(jnp.int32)
        c32 = state.head_cost.astype(jnp.int32)

        def body(c, _):
            t, _x = c
            key = state.head_prop + state.prop_delta + t
            kmin = jnp.min(key)
            k32 = jnp.clip(key - kmin, 0, (1 << 31) - 2).astype(jnp.int32)
            r32 = k32 + jnp.int32(1)         # stand-in reentry payload
            ks, os_, idxs, cs, rs = lax.sort(
                (k32, o32, iota, c32, r32), num_keys=2)
            pk = (ks[:k].astype(jnp.int64) << 32) | \
                (os_[:k].astype(jnp.int64) & 0xFFFFFFFF)
            rpk = (rs[:k].astype(jnp.int64) << 32)
            cm = lax.associative_scan(jnp.minimum, rpk)
            count = jnp.argmax(~(cm > pk)).astype(jnp.int32)
            return (t + idxs[0].astype(jnp.int64) + 1, _x), count
        return body
    measure_scan("selection: 5-array 2-key i32 sort + cummin",
                 sel_sort, state, jnp.int32(0))

    # -- radix replacement for the same job: histogram k-th boundary +
    # dense membership + compaction + [k]-sized sort (``_select_radix``
    # verbatim, so the row is the shipped code's cost, not a model)
    def sel_radix(state):
        iota = jnp.arange(n, dtype=jnp.int32)
        c32 = state.head_cost.astype(jnp.int32)
        omask = (jnp.int64(1) << 28) - 1

        def body(c, _):
            t, _x = c
            key = state.head_prop + state.prop_delta + t
            kmin = jnp.min(key)
            krel = jnp.clip(key - kmin, 0, (1 << 31) - 2)
            pk = (krel << 28) | (state.order & omask)
            epk = pk + 1                     # stand-in reentry payload
            pks, idxs, rpk, costs, lens = fastpath._select_radix(
                pk, iota, epk, c32, None, k, min(k, n))
            cm = lax.associative_scan(jnp.minimum, rpk)
            count = jnp.argmax(~(cm > pks)).astype(jnp.int32)
            return (t + idxs[0].astype(jnp.int64) + 1, _x), count
        return body
    measure_scan("selection: radix histogram k-select + [k] sort",
                 sel_radix, state, jnp.int32(0))

    # -- serve: dense elementwise retag (no ring access)
    def serve(state):
        n = state.capacity
        cls = jnp.full((n,), fastpath.CLS_WEIGHT, jnp.int32)

        def body(c, _):
            t, _x = c
            st = state._replace(prev_prop=state.prev_prop + t)
            sv = fastpath._chain_serve(
                st, jnp.int64(1 << 60), [st.head_arrival],
                [st.head_cost], cls, False, 0)
            return (t + sv.head_prop[0] + 1, _x), sv.head_resv[0]
        return body
    measure_scan("serve: dense elementwise retag", serve, state,
                 jnp.int32(0))

    # -- ring window: prefetch (per epoch) and select (per batch)
    def prefetch(state):
        def body(c, _):
            t, _x = c
            st = state._replace(q_head=(state.q_head + jnp.int32(t)) % 128)
            win = fastpath.ring_window(st, 32)
            return (t + win.arr[0, 0] + 1, _x), win.cost[0, 0]
        return body
    measure_scan("ring_window prefetch (barrel shift, per EPOCH)",
                 prefetch, state, jnp.int32(0))

    win = jax.jit(lambda s: fastpath.ring_window(s, 32))(state)

    def select(state):
        def body(c, _):
            t, _x = c
            st = state._replace(q_head=(state.q_head + jnp.int32(t)) % 128)
            narr, ncost = fastpath._window_heads(st, win)
            return (t + narr[0] + 1, _x), ncost[0]
        return body
    measure_scan("window head select (one-hot, per batch)", select,
                 state, jnp.int32(0))


if __name__ == "__main__":
    main()
