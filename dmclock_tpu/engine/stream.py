"""Always-on streaming serve loop: fused ingest+serve+commit chunks.

The round-based engine loop pays the host dispatch tax per epoch
THREE times over: a ``device_get(state.depth)`` for the host-side
admission clamp, an ``ingest_superwave`` launch, and the epoch-scan
launch (~17 ms per launch on the retired remote TPU runtime, not chip
evidence; priced continuously by the span tracer, ``obs.spans``).  This
module is the RackSched microsecond-dispatch thesis (PAPERS.md)
applied to that structure: ONE device launch runs a whole **stream
chunk** of epochs -- a ``lax.scan`` over epochs whose body fuses

1. the admission clamp (``min(raw_counts, min(ring - depth, waves))``
   computed ON DEVICE from the carried state, the same integer math
   the host clamp does, so the ingested counts are bit-identical),
2. ``kernels.ingest_superwave`` (the superwave ring pass), and
3. one full epoch of any of the three epoch engines
   (``fastpath.scan_prefix_epoch`` / ``scan_chain_epoch`` /
   ``scan_calendar_epoch``, all fast paths included),

with the decision stream, the per-epoch metric vectors, and the PR-6
telemetry accumulators (histograms / ledger / flight ring) stacking
up in HBM as scan outputs.  The host only uploads the PRE-GENERATED
raw Poisson draws (state-independent, so they can be drawn for chunk
T+1 while the device runs chunk T -- the double buffer) and drains
the stacked outputs at chunk boundaries, which the supervisor aligns
with its PR-5 checkpoint boundaries so crash equivalence survives the
refactor unchanged.

Everything in the decision path is integer (int64/int32/bool) ops, so
running the SAME epoch scans inside a bigger jit cannot perturb a
decision: the stream loop is digest-pinned bit-identical to the
round-based engine (tests/test_stream.py, ci.sh streaming smoke).

Layering: this module owns the pure device program + the host-side
epoch views that reconstruct per-epoch results for the chain digest;
``robust.guarded.run_stream_chunk_guarded`` adds retry + the
guard-trip fallback (a tripped chunk is discarded and re-run on the
proven round path); ``robust.supervisor`` drives chunks between
checkpoint boundaries.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.compile_plane import phase_scope
from . import kernels
from . import fastpath
from .state import EngineState


class StreamChunk(NamedTuple):
    """One fused chunk's device outputs.

    ``outs`` is a dict of per-epoch arrays stacked on a leading
    ``[epochs]`` axis -- exactly the fields the matching epoch-result
    class carries (see :data:`STREAM_OUT_FIELDS`), plus ``"metrics"``
    (``int64[epochs, NUM_METRICS]``; zeros when ``with_metrics`` is
    off).  Slicing epoch ``i`` out of every field reconstructs that
    epoch's result bit-for-bit (:func:`epoch_view`)."""

    state: EngineState
    outs: dict
    hists: object = None
    ledger: object = None
    flight: object = None
    slo: object = None
    prov: object = None


# per-engine stacked output fields, in the epoch-result class's field
# order (minus state/metrics/telemetry, which ride separately)
STREAM_OUT_FIELDS = {
    "prefix": ("count", "guards_ok", "slot", "phase", "cost", "lb"),
    "chain": ("count", "unit_count", "guards_ok", "slot", "cls",
              "length"),
    "calendar": ("count", "resv_count", "progress_ok", "served",
                 "level_count"),
}

# the guard vector each engine exposes (run_epoch_guarded's contract:
# any False means the epoch needs the host fallback path)
STREAM_GUARD_FIELD = {"prefix": "guards_ok", "chain": "guards_ok",
                      "calendar": "progress_ok"}


def clamped_ingest(state: EngineState, counts, t_base, *, waves: int,
                   dt_wave: int) -> EngineState:
    """The admission clamp + superwave ingest, ON DEVICE: the host
    clamp's integer math ``min(raw, min(ring - depth, waves))`` over
    the carried depth, then :func:`kernels.ingest_superwave` at wave
    times ``t_base + j * dt_wave``.  The ONE implementation shared by
    the fused chunk body and the guarded runner's standalone fallback
    leg (:func:`jit_ingest_step`) -- their bit-identity contract is
    that both ingest exactly what the round loop's host clamp would
    have, so the clamp must not be able to drift between them."""
    n = state.capacity
    with phase_scope("ingest"):
        cost1 = jnp.ones((n,), dtype=jnp.int64)
        headroom = jnp.minimum(
            jnp.int32(state.ring_capacity) - state.depth,
            jnp.int32(waves))
        c = jnp.minimum(counts, headroom)
        wave_times = jnp.asarray(t_base, jnp.int64) + jnp.arange(
            waves, dtype=jnp.int64) * dt_wave
        return kernels.ingest_superwave(
            state, c, wave_times, cost1, cost1, cost1,
            anticipation_ns=0)


def make_epoch_step(*, engine: str, m: int, kw: dict, dt_epoch_ns: int,
                    waves: int, ingest: bool,
                    with_pressure: bool = False):
    """The ONE fused per-epoch step shared by the stream chunk body
    and the mesh serving plane's per-shard chunk
    (``parallel.mesh.build_mesh_chunk``): clamped superwave ingest at
    ``t_base`` + one full epoch of ``engine`` serving at ``t_base +
    dt`` with the telemetry accumulators riding the carry.  Factoring
    it here is what makes the S=1 mesh == stream bit-identity a
    construction, not a test-only coincidence -- the two loops cannot
    drift because they trace the same step.

    ``with_pressure`` adds a MID-EPOCH pressure probe
    (``obs.provenance.pressure_vec`` on the post-ingest pre-serve
    state, at the epoch's serve time): the one instant where arrivals
    are queued but not yet drained, which is what makes the probe a
    real backlog signal on the calendar engines too -- their deadline
    commits drain ``state.depth`` within the epoch, so any
    boundary-time depth read is structurally zero there.  The probe is
    a pure integer read (no state change, no collective); it rides
    ``outs["pressure"]`` (``int64[PRESS_FIELDS]``) and is ignored by
    the digest's epoch views.

    Returns ``step(state, t_base, counts_e, hists, ledger, flight,
    slo, prov) -> ((state', hists', ledger', flight', slo', prov'),
    outs)`` with ``outs`` the engine's :data:`STREAM_OUT_FIELDS` plus
    ``"metrics"``."""
    fn = fastpath.epoch_scan_fn(engine)
    fields = STREAM_OUT_FIELDS[engine]
    dt = int(dt_epoch_ns)
    dt_wave = dt // int(waves)
    if with_pressure:
        from ..obs import provenance as _prov

    def step(st, t_base, counts_e, h, l, f, s, p):
        if ingest:
            st = clamped_ingest(st, counts_e, t_base,
                                waves=waves, dt_wave=dt_wave)
        if with_pressure:
            press = _prov.pressure_vec(st, t_base + dt)
        ep = fn(st, t_base + dt, m=m, **kw,
                hists=h, ledger=l, flight=f, slo=s, prov=p)
        outs = {name: getattr(ep, name) for name in fields}
        outs["metrics"] = ep.metrics
        if with_pressure:
            outs["pressure"] = press
        return (ep.state, ep.hists, ep.ledger, ep.flight,
                ep.slo, ep.prov), outs

    return step


def build_stream_chunk(*, engine: str, epochs: int, m: int, k: int = 0,
                       chain_depth: int = 4, dt_epoch_ns: int,
                       waves: int, anticipation_ns: int = 0,
                       allow_limit_break: bool = False,
                       with_metrics: bool = True,
                       select_impl: str = "sort", tag_width: int = 64,
                       window_m: Optional[int] = None,
                       calendar_impl: str = "minstop",
                       ladder_levels: int = 8,
                       wheel_kernel: str = "xla",
                       pull_budget: Optional[int] = None,
                       ingest: bool = True):
    """Build the pure chunk program ``(state, epoch0, counts, hists,
    ledger, flight) -> StreamChunk`` for one static configuration.

    ``epoch0`` is a TRACED int64 scalar (the chunk's first epoch
    index), so one compiled program serves every chunk of the same
    length; ``counts`` is ``int32[epochs, N]`` of RAW Poisson draws
    (``None`` and ``ingest=False`` for serve-only streams).  Epoch
    ``i`` ingests at ``t_base = (epoch0 + i) * dt_epoch_ns`` (wave
    times ``t_base + j * (dt_epoch_ns // waves)``) and serves at
    ``t_base + dt_epoch_ns`` -- the exact round-loop schedule
    (``robust.supervisor._job_loop``).  ``pull_budget`` (calendar
    engine only) caps each epoch's pulls
    (``fastpath.scan_calendar_epoch``)."""
    assert engine in fastpath.EPOCH_ENGINES, engine
    epochs = int(epochs)
    assert epochs >= 1, "a stream chunk needs at least one epoch"
    kw = fastpath.epoch_scan_kwargs(
        engine, k=k, chain_depth=chain_depth, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel, pull_budget=pull_budget,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics)
    dt = int(dt_epoch_ns)
    epoch_step = make_epoch_step(engine=engine, m=m, kw=kw,
                                 dt_epoch_ns=dt, waves=waves,
                                 ingest=ingest)

    def chunk(state: EngineState, epoch0, counts, hists=None,
              ledger=None, flight=None, slo=None,
              prov=None) -> StreamChunk:
        epoch0 = jnp.asarray(epoch0, dtype=jnp.int64)

        def body(carry, xs):
            st, h, l, f, s, p = carry
            counts_e, i = xs
            t_base = (epoch0 + i) * dt
            return epoch_step(st, t_base, counts_e, h, l, f, s, p)

        idx = jnp.arange(epochs, dtype=jnp.int64)
        if ingest:
            assert counts is not None, "ingest=True needs raw counts"
            xs = (counts, idx)
        else:
            xs = (jnp.zeros((epochs, 0), dtype=jnp.int32), idx)
        (state, hists, ledger, flight, slo, prov), outs = lax.scan(
            body, (state, hists, ledger, flight, slo, prov), xs)
        return StreamChunk(state=state, outs=outs, hists=hists,
                           ledger=ledger, flight=flight, slo=slo,
                           prov=prov)

    return chunk


# module-level jit cache keyed by the full static configuration (the
# engine/queue.py convention): a fresh jax.jit per chunk would
# recompile the whole fused program on every launch.  Entries are
# compile-plane-instrumented (obs.compile_plane): the fused chunk is
# the most expensive program in the repo to compile, so its
# lower+compile wall and retraces are exactly what the capacity plane
# must see.
_STREAM_JIT_CACHE: dict = {}


def jit_stream_chunk(*, donate: bool = False, **cfg):
    """Jitted :func:`build_stream_chunk` for ``cfg``.  ``donate=True``
    donates the state + telemetry accumulators (carried HBM state, the
    bench discipline); the guarded runner keeps them alive instead so
    a tripped chunk can be discarded and re-run from its entry state."""
    from ..obs import compile_plane as _cplane

    key = (donate,) + tuple(sorted(cfg.items()))
    if key not in _STREAM_JIT_CACHE:
        fn = build_stream_chunk(**cfg)
        donate_argnums = (0, 3, 4, 5, 6, 7) if donate else ()
        _STREAM_JIT_CACHE[key] = _cplane.instrumented_jit(
            fn, cache="stream.chunk", entry=key,
            donate_argnums=donate_argnums)
    return _STREAM_JIT_CACHE[key]


_INGEST_STEP_CACHE: dict = {}


def jit_ingest_step(*, dt_epoch_ns: int, waves: int):
    """One fused clamp+superwave ingest launch ``(state, raw_counts,
    t_base) -> state`` -- the stream chunk's ingest leg standing
    alone, for the guarded runner's round-path fallback (identical
    clamp math, so the fallback ingests exactly what the chunk would
    have)."""
    from ..obs import compile_plane as _cplane

    key = (int(dt_epoch_ns), int(waves))
    if key not in _INGEST_STEP_CACHE:
        dt_wave = int(dt_epoch_ns) // int(waves)

        def step(state: EngineState, counts, t_base):
            return clamped_ingest(state, counts, t_base,
                                  waves=waves, dt_wave=dt_wave)

        _INGEST_STEP_CACHE[key] = _cplane.instrumented_jit(
            step, cache="stream.ingest", entry=key)
    return _INGEST_STEP_CACHE[key]


def epoch_view(engine: str, outs: dict, i: int):
    """Reconstruct epoch ``i``'s result object from the fetched
    stacked chunk outputs -- the SAME result class the round-based
    epoch scan returns (``state=None``; nobody hashes or folds it), so
    the supervisor's chain digest (``_digest_update``'s
    ``hasattr``-driven field walk) sees byte-identical arrays in the
    identical field layout."""
    fields = {name: outs[name][i] for name in STREAM_OUT_FIELDS[engine]}
    metrics = outs["metrics"][i]
    if engine == "prefix":
        return fastpath.PrefixEpoch(state=None, metrics=metrics,
                                    **fields)
    if engine == "chain":
        return fastpath.ChainEpoch(state=None, metrics=metrics,
                                   **fields)
    return fastpath.CalendarEpoch(state=None, metrics=metrics,
                                  **fields)


def chunk_bounds(start: int, epochs: int, every: int):
    """Yield ``(e0, e1)`` stream-chunk windows from ``start`` to
    ``epochs``, each ending at the next PR-5 checkpoint boundary
    (``(e + 1) % every == 0`` or the final epoch) -- so a chunk drain
    IS a checkpoint drain and crash equivalence needs no new
    machinery.  Handles any ``start`` (a resume lands on a snapshot's
    epoch, always a boundary of this same layout)."""
    every = max(int(every), 1)
    e = int(start)
    while e < epochs:
        b = min((e // every + 1) * every, epochs)
        yield e, b
        e = b


def epoch_decisions(engine: str, outs: dict, i: int) -> int:
    """Decisions epoch ``i`` committed (the ``GuardedEpoch.count``
    mirror): the sum of the per-batch commit counts."""
    import numpy as np

    return int(np.asarray(outs["count"][i]).sum())
