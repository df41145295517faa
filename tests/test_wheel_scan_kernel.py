"""The Pallas ``wheel_scan`` kernel against its XLA twin, bit for bit.

``kernels_pallas.wheel_scan_pallas(keys, slot, nb)`` runs here in
interpret mode and must return exactly ``kernels.wheel_scan(keys,
slot, nb)``: the per-bucket counts, the per-bucket int64 minima, the
nearest value and the found flag.  The cases put the kernel's edges
under load: lane counts that are not a multiple of 128 (the wrapper
pads the last lane row and the row count), bucket counts that span
several bucket blocks, masked lanes (``slot == nb``), negative keys,
low words on both sides of 0x8000_0000 (the biased split), high-word
ties spread over rows and blocks whose low words alone decide the
minimum, and a bucket that only the last, padded row fills.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dmclock_tpu.engine import kernels
from dmclock_tpu.engine import kernels_pallas as KP

LANES = 128
# low words around the sign bit of the biased split, and the ends
EDGE_LO = np.array([0, 1, 0x7FFF_FFFE, 0x7FFF_FFFF, 0x8000_0000,
                    0x8000_0001, 0xFFFF_FFFE, 0xFFFF_FFFF],
                   dtype=np.int64)


def _keys(hi, lo):
    return hi.astype(np.int64) * (1 << 32) + lo.astype(np.int64)


def _case(n: int, nb: int, seed: int):
    """Random lanes over a few high words (so ties are common), about
    one in five masked, then planted structure where n allows it."""
    rng = np.random.default_rng(seed)
    hi = rng.choice(np.array([-(1 << 31), -7, -1, 0, 1, 3, 1 << 20],
                             dtype=np.int64), n)
    lo = np.where(rng.random(n) < 0.5, rng.choice(EDGE_LO, n),
                  rng.integers(0, 1 << 32, n, dtype=np.int64))
    slot = rng.integers(0, nb - 1, n).astype(np.int64)
    slot[rng.random(n) < 0.2] = nb
    last = (n - 1) // LANES * LANES      # first lane of the last row
    # ties on the least high word in buckets of the first, a middle
    # and the last block: one lane in each row before the last, low
    # words falling row by row, so the last tied row holds the min
    lanes = np.arange(0, max(last, 1), LANES)
    for b in sorted({0, nb // 2 - 1, nb // 2, nb - 2}):
        lanes = lanes[lanes < n]
        hi[lanes] = -(1 << 31)
        lo[lanes] = np.linspace(0xFFFF_FFFF, 0x7FFF_FFFF,
                                lanes.size).astype(np.int64)
        slot[lanes] = b
        lanes = lanes + 1
    if n > LANES:
        # bucket nb - 1: only the last, padded row fills it
        slot[last:] = np.where(np.arange(last, n) % 3 == 0, nb - 1, nb)
    return jnp.asarray(_keys(hi, lo)), jnp.asarray(slot, jnp.int32)


def _assert_same(keys, slot, nb):
    want = kernels.wheel_scan(keys, slot, nb)
    got = KP.wheel_scan_pallas(keys, slot, nb, interpret=True)
    for name, w, g in zip(("cnt", "bmin", "nearest", "found"), want, got):
        assert w.dtype == g.dtype, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    return want


@pytest.mark.parametrize("n", [1, 127, 128, 129, 1000, 3000])
@pytest.mark.parametrize("nb", [8, 256, 768])
def test_pallas_wheel_scan_equals_xla(nb, n):
    keys, slot = _case(n, nb, seed=1000 * nb + n)
    cnt, _bmin, _val, found = _assert_same(keys, slot, nb)
    assert int(cnt.sum()) == int((slot < nb).sum())
    assert bool(found) == bool((slot < nb).any())


@pytest.mark.parametrize("nb", [8, 256, 768])
def test_pallas_wheel_scan_all_masked(nb):
    keys, _ = _case(1000, nb, seed=nb)
    slot = jnp.full((1000,), nb, jnp.int32)
    cnt, bmin, val, found = _assert_same(keys, slot, nb)
    assert not bool(found)
    assert int(val) == kernels.KEY_INF
    assert not np.asarray(cnt).any()
    assert (np.asarray(bmin) == kernels.KEY_INF).all()
