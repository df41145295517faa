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

Shape: the kernel is gridless (gridding it is open perf work) and
loops the lane rows with ``lax.fori_loop``; iotas are 2-D; all
temporaries are [sublane, lane] shaped with the bucket axis on
sublanes, which makes the per-row one-hot compare a plain broadcast
with no transposes.  tests/test_tpu_compile.py compiles it for v5e
at n=100k, 3x256 buckets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .kernels import KEY_INF, WHEEL_GROUPS

_LANES = 128
_I32_MAX = 0x7FFFFFFF
# padded-lane budget for the gridless call: inputs are 3 int32 planes
# (12 B/lane) and the one-hot temp is [nb, 128]; 2^19 lanes keeps the
# whole working set well under the 16MB scoped-VMEM budget
MAX_LANES = 1 << 19


def wheel_supported(n: int, nb: int) -> bool:
    """Static feasibility of the gridless kernel at [n] lanes and
    ``nb`` buckets (a "pallas" request past it raises)."""
    padded = -(-n // _LANES) * _LANES
    return padded <= MAX_LANES and nb % 8 == 0


def _wheel_kernel(bidx_ref, khi_ref, klo_ref, cnt_ref, mhi_ref,
                  mlo_ref, near_ref, *, nb: int, rows: int):
    i32max = jnp.int32(_I32_MAX)
    bid = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)

    # phase A: occupancy count + per-bucket min of the high words.
    # One fori_loop over the [rows, 128] lane grid; each row compares
    # its 128 lane bucket ids against the [nb, 1] bucket column --
    # a broadcast one-hot, reduced along lanes.
    def phase_a(r, c):
        cnt, mhi = c
        oh = bidx_ref[pl.dslice(r, 1), :] == bid        # [nb, 128]
        hi = khi_ref[pl.dslice(r, 1), :]
        cnt = cnt + jnp.sum(oh, axis=1, keepdims=True,
                            dtype=jnp.int32)
        mhi = jnp.minimum(mhi, jnp.min(
            jnp.where(oh, hi, i32max), axis=1, keepdims=True))
        return cnt, mhi

    cnt, mhi = lax.fori_loop(
        0, rows, phase_a,
        (jnp.zeros((nb, 1), jnp.int32),
         jnp.full((nb, 1), i32max, jnp.int32)))

    # phase B: per-bucket min of the biased low words among the lanes
    # that tie the bucket's min high word (lex completion of the
    # int64 min; see module docstring)
    def phase_b(r, mlo):
        oh = bidx_ref[pl.dslice(r, 1), :] == bid
        tie = oh & (khi_ref[pl.dslice(r, 1), :] == mhi)
        return jnp.minimum(mlo, jnp.min(
            jnp.where(tie, klo_ref[pl.dslice(r, 1), :], i32max),
            axis=1, keepdims=True))

    mlo = lax.fori_loop(0, rows, phase_b,
                        jnp.full((nb, 1), i32max, jnp.int32))

    # fused occupancy-min-scan: first occupied bucket and its stored
    # minimum, before anything leaves the kernel
    occ = cnt > 0
    b0 = jnp.min(jnp.where(occ, bid, jnp.int32(nb)))
    at0 = occ & (bid == b0)
    nh = jnp.min(jnp.where(at0, mhi, i32max))
    nl = jnp.min(jnp.where(at0, mlo, i32max))
    found = (b0 < nb).astype(jnp.int32)

    cnt_ref[...] = cnt
    mhi_ref[...] = mhi
    mlo_ref[...] = mlo
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
    rows = -(-n // _LANES)
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
        interpret=interpret,
    )(slot.reshape(shape).astype(jnp.int32), khi.reshape(shape),
      klo.reshape(shape))
    cnt = cnt[:, 0]
    bmin = jnp.where(cnt > 0, _recon64(mhi[:, 0], mlo[:, 0]),
                     jnp.int64(KEY_INF))
    found = near[3, 0] > 0
    val = jnp.where(found, _recon64(near[1, 0], near[2, 0]),
                    jnp.int64(KEY_INF))
    return cnt, bmin, val, found
