#!/usr/bin/env python
"""Chain timing on the chip.

Every measurement here forces a ``device_get`` of a SCALAR digest
that data-depends on the full computation chain, and subtracts the
independently measured scalar round-trip latency.  Use long chains
(>= 1s of device work) so the residual noise is irrelevant."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

# the tag algebra is int64 ns end to end; enable x64 before any scalar
# below is created so callers importing this module first (the sweep
# scripts) don't silently truncate to int32
jax.config.update("jax_enable_x64", True)


@jax.jit
def state_digest(st):
    """Scalar that data-depends on every committed batch of an epoch
    chain (depth/head_prop/prev_resv are all mutated per commit)."""
    return st.depth.sum() + st.head_prop.sum() + st.prev_resv.sum()


def scalar_latency(reps: int = 5) -> float:
    """Round-trip cost of device_get on a freshly computed scalar."""
    x = jnp.int64(3)
    f = jax.jit(lambda v: v * 2 + 1)
    jax.device_get(f(x))
    t0 = time.perf_counter()
    v = x
    for _ in range(reps):
        v = f(v)
        jax.device_get(v)
    return (time.perf_counter() - t0) / reps


def timed_chain(step_fn, state0, n_steps: int, digest_fn,
                latency: float | None = None):
    """Run ``state = step_fn(state)`` n_steps times, then device_get
    ``digest_fn(state)`` (a jitted scalar).  Returns (seconds, digest),
    latency-corrected."""
    if latency is None:
        latency = scalar_latency()
    t0 = time.perf_counter()
    st = state0
    for _ in range(n_steps):
        st = step_fn(st)
    digest = jax.device_get(digest_fn(st))
    t = time.perf_counter() - t0 - latency
    return t, digest, st
