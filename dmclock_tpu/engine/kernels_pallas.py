"""Hand-written Pallas TPU kernels: the timer-wheel scatter/scan.

The repo's first real kernel (ROADMAP "hand-written kernel" item): the
calendar engine's bucket-scatter + occupancy-min-scan
(``kernels.wheel_scan``) fused into ONE gridless pallas_call -- one
read of the lane data from HBM, the whole bucket grid lives in VMEM,
and the nearest-deadline scan happens in-register before a single
store.  The XLA reference lowers the same computation to a scatter-add
+ scatter-min (serializing on TPU) followed by a separate reduction
pass; the kernel is the template for kernelizing the radix histogram
walk next.

Bit-exactness contract (ci.sh wheel smoke gate, interpret mode on
CPU): for any ``(keys, slot, nb)`` the kernel returns EXACTLY
``kernels.wheel_scan(keys, slot, nb)`` -- counts, per-bucket minima,
nearest value, and the found flag.  The int64 keys travel as int32
(hi, lo) lane pairs: ``hi = key >> 32`` keeps the sign, and the low
word is XOR-biased (``lo ^ 0x8000_0000`` wrapped to int32) so SIGNED
int32 comparison of the biased low words equals UNSIGNED comparison
of the raw ones -- the (hi signed, lo unsigned) lexicographic order
IS the int64 order, so per-bucket (min hi, min lo among hi-ties)
reconstructs the exact int64 minimum.

Shape: the kernel is gridless; the lane data sits in VMEM as three
[rows, 128] int32 planes (the wrapper pads the row count to the loop's
unroll, padded lanes masked).  The buckets are taken in blocks of up
to ``_BLOCK`` (64, 8 vregs): for each block one ``lax.fori_loop`` over
the lane rows broadcasts each row across the block's bucket sublanes
and keeps LANE-WISE [block, 128] partials -- count += one-hot, high
word min = min(min, where(one-hot, hi, MAX)) -- in vregs.  Count and
min are associative and exact in int32, so the lanes are reduced once
per block, after its rows, not once per row; a second row loop then
takes the lane-wise min low word among the block's high-word ties and
reduces it once.  The block writes its rows of the [nb, 1] count and
min columns, and the first-occupied-bucket scan runs on the finished
columns.  Iotas are 2-D and the bucket axis is on sublanes, so the
one-hot compare is a plain broadcast with no transposes.
tests/test_tpu_compile.py compiles it for v5e at n=100k (3x256 and
256 buckets) and at the lane budget; tests/test_wheel_scan_kernel.py
holds it to ``kernels.wheel_scan`` bit for bit in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .kernels import KEY_INF, WHEEL_GROUPS

_LANES = 128
_I32_MAX = 0x7FFFFFFF
# padded-lane budget for the gridless call: inputs are 3 int32 planes
# (12 B/lane, 6 MB at the budget) and everything else is [nb, 1]
# columns or a bucket block's [_BLOCK, 128] partials in vregs, so the
# working set stays well under the 16MB scoped-VMEM budget
MAX_LANES = 1 << 19
# buckets per block (a power of two; a block of nb is the largest that
# divides it): each lane-wise partial is _BLOCK / 8 vregs, and a block
# carries two of them plus its bucket ids through the row loop
_BLOCK = 64
# lane rows per loop step (the wrapper pads the row count to it)
_UNROLL = 8


def wheel_supported(n: int, nb: int) -> bool:
    """Static feasibility of the gridless kernel at [n] lanes and
    ``nb`` buckets (a "pallas" request past it raises)."""
    padded = -(-n // _LANES) * _LANES
    return padded <= MAX_LANES and nb % 8 == 0


def _wheel_kernel(bidx_ref, khi_ref, klo_ref, cnt_ref, mhi_ref,
                  mlo_ref, near_ref, *, nb: int, rows: int):
    i32max = jnp.int32(_I32_MAX)
    block = math.gcd(nb, _BLOCK)     # 8 at least: nb % 8 == 0
    tile = (block, _LANES)
    zero = jnp.int32(0)
    steps = jnp.int32(rows // _UNROLL)

    def lane_rows(ref, g):
        # the step's _UNROLL lane rows, each broadcast over the
        # block's bucket sublanes
        return [jnp.broadcast_to(ref[pl.dslice(g * _UNROLL + j, 1), :],
                                 tile) for j in range(_UNROLL)]

    def bucket_block(b, carry):
        base = pl.multiple_of(b * block, block)
        bid = lax.broadcasted_iota(jnp.int32, tile, 0) + base

        # phase A: occupancy count + min high word, kept lane-wise
        # ([block, 128] partials) across every row; count and min are
        # associative and exact, so the lanes are reduced once, after
        # the rows
        def phase_a(g, c):
            cnt, mhi = c
            for ids, hi in zip(lane_rows(bidx_ref, g),
                               lane_rows(khi_ref, g)):
                oh = ids == bid
                cnt = cnt + oh.astype(jnp.int32)
                mhi = jnp.minimum(mhi, jnp.where(oh, hi, i32max))
            return cnt, mhi

        cnt, mhi = lax.fori_loop(
            zero, steps, phase_a,
            (jnp.zeros(tile, jnp.int32), jnp.full(tile, i32max, jnp.int32)))
        cnt = jnp.sum(cnt, axis=1, keepdims=True, dtype=jnp.int32)
        mhi = jnp.min(mhi, axis=1, keepdims=True)
        mhi_lanes = jnp.broadcast_to(mhi, tile)

        # phase B: min biased low word among the lanes that tie their
        # bucket's min high word (lex completion of the int64 min; see
        # module docstring), lane-wise again
        def phase_b(g, mlo):
            for ids, hi, lo in zip(lane_rows(bidx_ref, g),
                                   lane_rows(khi_ref, g),
                                   lane_rows(klo_ref, g)):
                tie = (ids == bid) & (hi == mhi_lanes)
                mlo = jnp.minimum(mlo, jnp.where(tie, lo, i32max))
            return mlo

        mlo = lax.fori_loop(zero, steps, phase_b,
                            jnp.full(tile, i32max, jnp.int32))
        cnt_ref[pl.dslice(base, block), :] = cnt
        mhi_ref[pl.dslice(base, block), :] = mhi
        mlo_ref[pl.dslice(base, block), :] = jnp.min(
            mlo, axis=1, keepdims=True)
        return carry

    lax.fori_loop(zero, jnp.int32(nb // block), bucket_block, zero)

    # fused occupancy-min-scan over the finished columns: first
    # occupied bucket and its stored minimum, before anything leaves
    # the kernel
    bid = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cnt, mhi, mlo = cnt_ref[...], mhi_ref[...], mlo_ref[...]
    occ = cnt > 0
    b0 = jnp.min(jnp.where(occ, bid, jnp.int32(nb)))
    at0 = occ & (bid == b0)
    nh = jnp.min(jnp.where(at0, mhi, i32max))
    nl = jnp.min(jnp.where(at0, mlo, i32max))
    found = (b0 < nb).astype(jnp.int32)

    lane = lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    near_ref[...] = jnp.where(
        lane == 0, b0,
        jnp.where(lane == 1, nh,
                  jnp.where(lane == 2, nl,
                            jnp.where(lane == 3, found,
                                      jnp.int32(0)))))


def _recon64(hi, lo_biased):
    """Invert the (hi, biased lo) int32 split back to int64."""
    lo = lo_biased.astype(jnp.int64) + jnp.int64(1 << 31)
    return (hi.astype(jnp.int64) << 32) | lo


def wheel_scan_pallas(keys, slot, nb: int, *,
                      groups: int = WHEEL_GROUPS,
                      interpret: bool = False):
    """Pallas twin of :func:`kernels.wheel_scan`: scatter ``keys``
    into ``nb`` buckets by ``slot`` (``slot == nb`` masks a lane out)
    and scan for the first occupied bucket.  Returns ``(cnt int32[nb],
    bmin int64[nb], nearest int64, found bool)`` bit-identical to the
    XLA reference.  ``groups`` is accepted for signature parity (the
    in-kernel scan needs no grouping)."""
    del groups
    n = keys.shape[0]
    rows = -(-n // (_LANES * _UNROLL)) * _UNROLL
    pad = rows * _LANES - n
    if pad:
        keys = jnp.pad(keys, (0, pad))
        slot = jnp.pad(slot, (0, pad), constant_values=nb)
    khi = (keys >> 32).astype(jnp.int32)
    klo = ((keys & jnp.int64(0xFFFFFFFF))
           ^ jnp.int64(0x80000000)).astype(jnp.int32)
    shape = (rows, _LANES)
    out1 = jax.ShapeDtypeStruct((nb, 1), jnp.int32)
    cnt, mhi, mlo, near = pl.pallas_call(
        functools.partial(_wheel_kernel, nb=nb, rows=rows),
        out_shape=[out1, out1, out1,
                   jax.ShapeDtypeStruct((8, 1), jnp.int32)],
        interpret=interpret, name="wheel_scan",
    )(slot.reshape(shape).astype(jnp.int32), khi.reshape(shape),
      klo.reshape(shape))
    cnt = cnt[:, 0]
    bmin = jnp.where(cnt > 0, _recon64(mhi[:, 0], mlo[:, 0]),
                     jnp.int64(KEY_INF))
    found = near[3, 0] > 0
    val = jnp.where(found, _recon64(near[1, 0], near[2, 0]),
                    jnp.int64(KEY_INF))
    return cnt, bmin, val, found
