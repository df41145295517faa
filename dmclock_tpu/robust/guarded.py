"""The guarded-commit contract: trip -> commit nothing -> retry.

Generalizes the tag32 ``rebase_fallbacks`` pattern (docs/ENGINE.md)
into one repo-wide contract, documented in docs/ROBUSTNESS.md:

1. **Device side** -- an engine step that trips a guard (int32
   tag-window overflow, creation-order/cost rebase guard, calendar
   no-progress) commits *nothing* from that trip: the scan carry keeps
   the last good state, ``guards_ok``/``progress_ok`` reads False, and
   a fault counter bumps.  This is already built into the epoch scans;
   :func:`run_epoch_guarded` is the host half that resumes the
   remaining batches on the always-exact path.
2. **Host side** -- transient device failures (a wedged launch, a
   runtime OOM-and-recover) are retried with **bounded
   exponential backoff** instead of raising out of the serving layer:
   :func:`retry_with_backoff`, used by ``engine.queue
   .TpuPullPriorityQueue`` around every device launch.  State is only
   rebound on success (jax programs are pure), so a failed launch
   never half-commits.

This module must stay import-light: ``engine.queue`` imports it, so
anything from ``engine`` is imported lazily inside functions.
"""

from __future__ import annotations

import time as _time
from typing import Callable, NamedTuple, Optional

from jax.errors import JaxRuntimeError

# Exception classes worth retrying: jax DEVICE errors (JaxRuntimeError
# -- a wedged launch) and transport failures (OSError covers
# ConnectionError; TimeoutError).  Plain RuntimeError is deliberately
# NOT in the set: a generic host-side RuntimeError is a caller bug, and
# retrying it would just re-raise the same error after three backoff
# sleeps under the queue lock.
RECOVERABLE_ERRORS = (OSError, TimeoutError, JaxRuntimeError)


def retry_with_backoff(fn: Callable, *, retries: int = 3,
                       base_s: float = 0.05, factor: float = 2.0,
                       max_s: float = 2.0,
                       recoverable=RECOVERABLE_ERRORS,
                       on_retry: Optional[Callable[[int, BaseException],
                                                   None]] = None,
                       sleep: Callable[[float], None] = _time.sleep,
                       jitter_seed: Optional[int] = None,
                       deadline_s: Optional[float] = None,
                       clock: Callable[[], float] = _time.monotonic):
    """Call ``fn()``; on a recoverable error sleep
    ``min(base_s * factor**i, max_s)`` and retry, at most ``retries``
    times, then re-raise the last error.  ``on_retry(attempt, exc)``
    observes each retry (the queue counts them into its metrics).
    ``fn`` must be pure/idempotent -- jitted device launches are.

    ``jitter_seed`` (anti-thundering-herd): scale every sleep by a
    DETERMINISTIC per-seed multiplier in ``[0.5, 1.5)`` (PCG64, stable
    across runs/platforms -- the host-fault-plan convention), so S
    shards relaunching after one shared device wedge desynchronize by
    seeding with their shard index instead of stampeding the runtime
    in lockstep.  Unseeded behavior is the exact historical schedule.

    ``deadline_s``: an overall wall-clock budget measured by
    ``clock()`` (injectable for tests).  Once spent, the next
    recoverable error re-raises even with retries left, and any final
    sleep is truncated to the remaining budget -- bounded total stall,
    retries or not."""
    rng = None
    if jitter_seed is not None:
        import numpy as _np
        rng = _np.random.Generator(_np.random.PCG64(int(jitter_seed)))
    t0 = clock() if deadline_s is not None else 0.0
    attempt = 0
    while True:
        try:
            return fn()
        except recoverable as e:  # noqa: PERF203 -- the whole point
            if attempt >= retries:
                raise
            if deadline_s is not None and clock() - t0 >= deadline_s:
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            delay = min(base_s * (factor ** attempt), max_s)
            if rng is not None:
                delay *= 0.5 + rng.random()
            if deadline_s is not None:
                delay = min(delay, max(deadline_s - (clock() - t0),
                                       0.0))
            sleep(delay)
            attempt += 1


class GuardedEpoch(NamedTuple):
    """Result of :func:`run_epoch_guarded`."""

    state: object            # EngineState after every committed batch
    count: int               # decisions committed (incl. the resume)
    results: tuple           # the raw epoch result(s), in run order
    rebase_fallbacks: int    # tag32 window trips resumed on int64
    serial_fallbacks: int    # order/cost guard trips resumed serially
    retries: int             # transient device errors retried
    # telemetry accumulators after the LAST scan attempt (pass-through
    # state: a tag32 resume continues accumulating from the first
    # attempt's outputs; the rare serial fallback's decisions are not
    # telemetered -- docs/OBSERVABILITY.md).  None when the caller
    # passed none in.
    hists: object = None
    ledger: object = None
    flight: object = None
    slo: object = None
    prov: object = None


# Module-level jit cache keyed by the static epoch configuration (the
# engine/queue.py _JIT_CACHE convention): a fresh jax.jit(partial(...))
# per call would retrace + recompile the whole epoch program on EVERY
# guarded run, and the compile dwarfs the epoch at bench shapes.
# Entries are compile-plane-instrumented (obs.compile_plane).
_EPOCH_JIT_CACHE: dict = {}


def _jit_epoch(engine: str, m_run: int, kw: dict, tele_sig=()):
    """``tele_sig`` is the tuple of telemetry accumulator names the
    wrapped call threads through as TRACED arguments (they must not be
    closed over -- a partial-bound array would constant-fold into the
    compiled program and break the module-cache reuse)."""
    key = (engine, m_run, tuple(sorted(kw.items())), tele_sig)
    if key not in _EPOCH_JIT_CACHE:
        import functools

        from ..engine import fastpath
        from ..obs import compile_plane as _cplane
        fn = fastpath.epoch_scan_fn(engine)
        if tele_sig:
            def run(st, t, tele):
                return fn(st, t, m=m_run, **kw, **tele)
            _EPOCH_JIT_CACHE[key] = _cplane.instrumented_jit(
                run, cache="guarded.epoch", entry=key)
        else:
            _EPOCH_JIT_CACHE[key] = _cplane.instrumented_jit(
                functools.partial(fn, m=m_run, **kw),
                cache="guarded.epoch", entry=key)
    return _EPOCH_JIT_CACHE[key]


def _jit_serial(steps: int, allow_limit_break: bool,
                anticipation_ns: int):
    key = ("serial", steps, allow_limit_break, anticipation_ns)
    if key not in _EPOCH_JIT_CACHE:
        import functools

        from ..engine import kernels
        from ..obs import compile_plane as _cplane
        _EPOCH_JIT_CACHE[key] = _cplane.instrumented_jit(
            functools.partial(
                kernels.engine_run, steps=steps,
                allow_limit_break=allow_limit_break,
                anticipation_ns=anticipation_ns, advance_now=False),
            cache="guarded.serial", entry=key)
    return _EPOCH_JIT_CACHE[key]


def _epoch_count(engine: str, result) -> int:
    import numpy as np
    return int(np.asarray(result.count).sum())


def _guard_vec(engine: str, result):
    import numpy as np
    ok = result.progress_ok if engine == "calendar" \
        else result.guards_ok
    return np.asarray(ok)


def _window_args(engine: str, m: int, state, window_m) -> dict:
    """Dispatch-span args of a prefix epoch: the ring-window width the
    scan prefetches (``fastpath.prefix_window_width``) and the
    prefetches per epoch.  Empty for the other engines, which prefetch
    per batch."""
    if engine != "prefix":
        return {}
    from ..engine import fastpath

    w = fastpath.prefix_window_width(m, state.ring_capacity,
                                     window_m=window_m)
    return {"window_m": w, "prefetches": m // w}


def run_epoch_guarded(state, now, *, engine: str = "prefix",
                      m: int, k: int = 0, chain_depth: int = 4,
                      anticipation_ns: int = 0,
                      allow_limit_break: bool = False,
                      with_metrics: bool = False,
                      select_impl: str = "sort",
                      tag_width: int = 64,
                      window_m: Optional[int] = None,
                      calendar_impl: str = "minstop",
                      ladder_levels: int = 8,
                      wheel_kernel: str = "xla",
                      pull_budget: Optional[int] = None,
                      skew_ns: int = 0,
                      hists=None, ledger=None, flight=None, slo=None,
                      prov=None,
                      retries: int = 3, base_s: float = 0.05,
                      sleep: Callable[[float], None] = _time.sleep,
                      on_retry=None, tracer=None) -> GuardedEpoch:
    """Run one epoch of any of the three epoch engines under the
    guarded-commit contract, host side included.

    The epoch itself enforces commit-nothing-on-trip; this wrapper (a)
    retries transient device failures with bounded backoff, (b) on a
    tag32 window trip resumes the REMAINING batches from the returned
    last-good state on the int64 path, and (c) on an order/cost guard
    trip (64-bit; never observed in practice) resumes on the serial
    engine -- the ``make_prefix_runner`` fallback generalized to all
    three engines.  ``skew_ns`` is the fault-injection hook: the epoch
    sees ``now + skew_ns``.  With ``skew_ns=0`` the first attempt is
    the untouched epoch call -- bit-identical to no wrapper at all
    (chaos differential gate).

    ``pull_budget`` (calendar engine only) caps the epoch at that many
    pulls, resumes included: a serial resume pops the budget left.

    ``hists`` / ``ledger`` / ``flight`` (None = off) are the telemetry
    accumulators of ``fastpath.scan_*_epoch``: pass-through state, so
    a tag32 window trip's int64 resume continues accumulating from
    the first attempt's outputs and the returned accumulators cover
    the whole epoch.  The serial-engine fallback (never observed in
    practice) passes them through untouched -- its decisions are not
    telemetered.

    ``tracer`` (``obs.spans.SpanTracer`` or None) records host spans
    around each launch -- ``guarded.dispatch`` (the jit call) and
    ``guarded.device_wait`` (the ``block_until_ready``) -- plus
    ``retry`` instants for backoff retries and the tag32/serial
    resumes.  Host-side only: decisions are bit-identical with or
    without it (ci.sh tracing smoke).
    """
    import jax
    import jax.numpy as jnp

    from ..engine import fastpath, kernels
    from ..obs import spans as _spans

    assert engine in fastpath.EPOCH_ENGINES, engine
    kw = fastpath.epoch_scan_kwargs(
        engine, k=k, chain_depth=chain_depth, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel, pull_budget=pull_budget,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics)
    retry_count = [0]

    def count_retry(attempt, exc):
        retry_count[0] += 1
        _spans.instant(tracer, "guarded.retry", "retry",
                       error=type(exc).__name__)
        if on_retry is not None:
            on_retry(attempt, exc)

    tele = {}
    if hists is not None:
        tele["hists"] = hists
    if ledger is not None:
        tele["ledger"] = ledger
    if flight is not None:
        tele["flight"] = flight
    if slo is not None:
        tele["slo"] = slo
    if prov is not None:
        tele["prov"] = prov
    tele_sig = tuple(sorted(tele))

    def attempt(st, t, m_run, width):
        fn = _jit_epoch(engine, m_run, {**kw, "tag_width": width},
                        tele_sig)
        call = (lambda: fn(st, t, tele)) if tele_sig \
            else (lambda: fn(st, t))

        def one():
            # dispatch (the async jit call) and the device wait are
            # separate spans: their ratio is the dispatch tax
            args = {} if tracer is None \
                else _window_args(engine, m_run, st, window_m)
            with _spans.span(tracer, "guarded.dispatch", "dispatch",
                             engine=engine, m=m_run, **args):
                out = call()
            with _spans.span(tracer, "guarded.device_wait",
                             "device_compute"):
                return jax.block_until_ready(out)

        return retry_with_backoff(
            one, retries=retries, base_s=base_s, sleep=sleep,
            on_retry=count_retry)

    def take_tele(ep):
        for name in tele_sig:
            tele[name] = getattr(ep, name)

    t = jnp.asarray(now, dtype=jnp.int64) + jnp.int64(skew_ns)
    results = []
    rebase_fb = serial_fb = 0
    ep = attempt(state, t, m, tag_width)
    results.append(ep)
    take_tele(ep)
    total = _epoch_count(engine, ep)
    state = ep.state
    guards = _guard_vec(engine, ep)
    if not guards.all():
        remaining = int(m - guards.sum())
        if tag_width == 32:
            # tag32 window trip: the batch committed nothing; resume
            # the remaining batches on the int64 path (exactness pinned
            # by tests/test_radix.py)
            rebase_fb = 1
            _spans.instant(tracer, "guarded.rebase_resume", "retry",
                           remaining=remaining)
            ep2 = attempt(state, t, remaining, 64)
            results.append(ep2)
            take_tele(ep2)
            g2 = _guard_vec(engine, ep2)
            total += _epoch_count(engine, ep2)
            state = ep2.state
            guards = g2
            remaining = int(remaining - g2.sum())
        if not guards.all():
            # order/cost guard (or calendar no-progress) on the exact
            # path: fall back to the serial engine for the rest
            serial_fb = 1
            _spans.instant(tracer, "guarded.serial_resume", "retry",
                           remaining=remaining)
            # (a budgeted calendar batch stalls only with budget left,
            # so a budgeted resume pops at least one)
            steps = max(remaining, 1) * max(k, 1) \
                if pull_budget is None else pull_budget - total
            run = _jit_serial(steps, allow_limit_break,
                              anticipation_ns)

            def serial_one():
                with _spans.span(tracer, "guarded.dispatch",
                                 "dispatch", engine="serial"):
                    out = run(state, t)
                with _spans.span(tracer, "guarded.device_wait",
                                 "device_compute"):
                    return jax.block_until_ready(out)

            st2, _, decs = retry_with_backoff(
                serial_one, retries=retries, base_s=base_s,
                sleep=sleep, on_retry=count_retry)
            import numpy as np
            total += int((np.asarray(decs.type)
                          == kernels.RETURNING).sum())
            state = st2
            results.append(decs)
    return GuardedEpoch(state=state, count=total,
                        results=tuple(results),
                        rebase_fallbacks=rebase_fb,
                        serial_fallbacks=serial_fb,
                        retries=retry_count[0],
                        hists=tele.get("hists"),
                        ledger=tele.get("ledger"),
                        flight=tele.get("flight"),
                        slo=tele.get("slo"),
                        prov=tele.get("prov"))


class StreamGuarded(NamedTuple):
    """Result of :func:`run_stream_chunk_guarded` -- one stream chunk
    of epochs, drained and normalized to per-epoch rows so the caller
    (``robust.supervisor``'s stream loop) runs the exact same chain
    digest / metric-fold / ladder bookkeeping as the round loop."""

    state: object            # EngineState after the whole chunk
    epochs: tuple            # per-epoch tuples of raw result objects
    #                          (digest-ready, run order -- exactly
    #                          what GuardedEpoch.results holds)
    counts: tuple            # per-epoch decisions committed (int)
    guard_trips: tuple       # per-epoch rebase+serial fallback count
    #                          (0 on a clean chunk)
    stream_fallback: int     # 1 when the chunk tripped a guard and
    #                          was discarded + re-run on the round
    #                          path (slower, never divergent)
    retries: int             # transient device errors retried
    hists: object = None     # telemetry accumulators after the chunk
    ledger: object = None
    flight: object = None
    slo: object = None
    prov: object = None


def run_stream_chunk_guarded(state, epoch0: int, counts, *,
                             engine: str, epochs: int, m: int,
                             k: int = 0, chain_depth: int = 4,
                             dt_epoch_ns: int, waves: int,
                             anticipation_ns: int = 0,
                             allow_limit_break: bool = False,
                             with_metrics: bool = True,
                             select_impl: str = "sort",
                             tag_width: int = 64,
                             window_m: Optional[int] = None,
                             calendar_impl: str = "minstop",
                             ladder_levels: int = 8,
                             wheel_kernel: str = "xla",
                             pull_budget: Optional[int] = None,
                             hists=None, ledger=None, flight=None,
                             slo=None, prov=None,
                             retries: int = 3, base_s: float = 0.05,
                             sleep: Callable[[float], None] =
                             _time.sleep,
                             on_retry=None, tracer=None,
                             overlap: Optional[Callable[[], None]]
                             = None) -> StreamGuarded:
    """Run one fused ingest+serve stream chunk (``engine.stream``)
    under the guarded-commit contract, at STREAM-CHUNK granularity:

    - the single chunk launch retries transient device failures with
      bounded backoff exactly like the per-epoch launches do;
    - ``overlap()`` (idempotent; may be None) is invoked after the
      launch is DISPATCHED and before the host blocks on it -- the
      double-buffer seam where the caller pre-generates chunk T+1's
      superwave draws while the device runs chunk T;
    - a guard trip ANYWHERE in the chunk (tag32 window, order/cost
      rebase, calendar no-progress) discards the whole chunk and
      re-runs its epochs one by one on the proven round path
      (``run_epoch_guarded``) from the retained entry state + entry
      telemetry -- bit-identical to the round loop by construction,
      since the round loop IS the fallback.  ``stream_fallback``
      reports it; the entry state/telemetry are therefore never
      donated to the chunk launch.

    ``counts`` is ``int32[epochs, N]`` of RAW (unclamped) Poisson
    draws, or None for a no-ingest stream; the chunk clamps on device
    with the identical integer math the round loop's host clamp uses.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..engine import stream as stream_mod
    from ..obs import spans as _spans

    epochs = int(epochs)
    do_ingest = counts is not None
    fn = stream_mod.jit_stream_chunk(
        engine=engine, epochs=epochs, m=m, k=k,
        chain_depth=chain_depth, dt_epoch_ns=dt_epoch_ns, waves=waves,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break, with_metrics=with_metrics,
        select_impl=select_impl, tag_width=tag_width,
        window_m=window_m, calendar_impl=calendar_impl,
        ladder_levels=ladder_levels, wheel_kernel=wheel_kernel,
        pull_budget=pull_budget, ingest=do_ingest, donate=False)
    retry_count = [0]

    def count_retry(attempt, exc):
        retry_count[0] += 1
        _spans.instant(tracer, "stream.retry", "retry",
                       error=type(exc).__name__)
        if on_retry is not None:
            on_retry(attempt, exc)

    with _spans.span(tracer, "stream.upload", "host_prep"):
        counts_dev = None if counts is None \
            else jnp.asarray(counts, dtype=jnp.int32)
    dispatch_args = {} if tracer is None \
        else _window_args(engine, m, state, window_m)

    def one():
        with _spans.span(tracer, "stream.dispatch", "dispatch",
                         engine=engine, epochs=epochs, **dispatch_args):
            out = fn(state, jnp.int64(epoch0), counts_dev,
                     hists, ledger, flight, slo, prov)
        if overlap is not None:
            overlap()     # host pregen rides the device's chunk time
        with _spans.span(tracer, "stream.device_wait",
                         "device_compute"):
            return jax.block_until_ready(out)

    out = retry_with_backoff(one, retries=retries, base_s=base_s,
                             sleep=sleep, on_retry=count_retry)

    guard_field = stream_mod.STREAM_GUARD_FIELD[engine]
    with _spans.span(tracer, "stream.fetch", "fetch", epochs=epochs):
        guards = np.asarray(jax.device_get(out.outs[guard_field]))
        if bool(guards.all()):
            fetched = jax.device_get(out.outs)
            views = tuple(stream_mod.epoch_view(engine, fetched, i)
                          for i in range(epochs))
            decided = tuple(stream_mod.epoch_decisions(engine, fetched,
                                                       i)
                            for i in range(epochs))
            return StreamGuarded(
                state=out.state, epochs=tuple((v,) for v in views),
                counts=decided, guard_trips=(0,) * epochs,
                stream_fallback=0, retries=retry_count[0], hists=out.hists,
                ledger=out.ledger, flight=out.flight, slo=out.slo,
                prov=out.prov)

    # a guard tripped somewhere in the chunk: the fused program cannot
    # run the tag32/serial resumes mid-scan, so the whole chunk is
    # discarded (its outputs never reach the digest) and its epochs
    # replay on the round path from the RETAINED entry state -- the
    # epochs before the trip recompute bit-identically (pure integer
    # programs), the tripped one resumes exactly as the round loop
    # would have
    _spans.instant(tracer, "stream.fallback", "retry", engine=engine,
                   epochs=epochs)
    ingest_step = stream_mod.jit_ingest_step(
        dt_epoch_ns=dt_epoch_ns, waves=waves) if do_ingest else None
    st = state
    cur = {"hists": hists, "ledger": ledger, "flight": flight,
           "slo": slo, "prov": prov}
    ep_rows, count_rows, trip_rows = [], [], []
    for i in range(epochs):
        t_base = (int(epoch0) + i) * int(dt_epoch_ns)
        if ingest_step is not None:
            st = ingest_step(st, counts_dev[i], jnp.int64(t_base))
        ep = run_epoch_guarded(
            st, t_base + int(dt_epoch_ns), engine=engine, m=m, k=k,
            chain_depth=chain_depth, anticipation_ns=anticipation_ns,
            allow_limit_break=allow_limit_break,
            with_metrics=with_metrics, select_impl=select_impl,
            tag_width=tag_width, window_m=window_m,
            calendar_impl=calendar_impl, ladder_levels=ladder_levels,
            wheel_kernel=wheel_kernel, pull_budget=pull_budget,
            hists=cur["hists"], ledger=cur["ledger"],
            flight=cur["flight"], slo=cur["slo"], prov=cur["prov"],
            retries=retries, base_s=base_s,
            sleep=sleep, on_retry=on_retry, tracer=tracer)
        st = ep.state
        if cur["hists"] is not None:
            cur["hists"] = ep.hists
        if cur["ledger"] is not None:
            cur["ledger"] = ep.ledger
        if cur["flight"] is not None:
            cur["flight"] = ep.flight
        if cur["slo"] is not None:
            cur["slo"] = ep.slo
        if cur["prov"] is not None:
            cur["prov"] = ep.prov
        retry_count[0] += ep.retries
        ep_rows.append(ep.results)
        count_rows.append(ep.count)
        trip_rows.append(ep.rebase_fallbacks + ep.serial_fallbacks)
    return StreamGuarded(
        state=st, epochs=tuple(ep_rows), counts=tuple(count_rows),
        guard_trips=tuple(trip_rows), stream_fallback=1,
        retries=retry_count[0], hists=cur["hists"],
        ledger=cur["ledger"], flight=cur["flight"], slo=cur["slo"],
        prov=cur["prov"])


class MeshGuarded(NamedTuple):
    """Result of :func:`run_mesh_chunk_guarded` -- one mesh chunk of
    epochs across all shards, drained and normalized to per-epoch rows.
    Each row is a tuple of PER-SHARD result-object tuples in SHARD
    ORDER (flatten a row for the chain digest; the grouping is what
    lets a churn job apply each shard's canonical slot->cid view to
    exactly that shard's results).  At S=1 a flattened row is exactly
    the stream loop's."""

    state: object            # stacked EngineState [S, ...]
    cd: object               # int64[S, N] completion counters
    cr: object
    view_d: object           # int64[S, N] held counter views
    view_r: object
    epochs: tuple            # per-epoch tuples of per-shard tuples
    counts: tuple            # per-epoch AGGREGATE decisions (int)
    guard_trips: tuple       # per-epoch rebase+serial fallback count
    mesh_fallback: int       # 1 when the chunk tripped a guard and
    #                          was discarded + re-run epoch-major on
    #                          the host robust loop (slower, never
    #                          divergent; under a fault plan the
    #                          supervisor counts it as a
    #                          mesh_chaos_fallback)
    retries: int
    hists: object = None     # stacked telemetry accumulators
    ledger: object = None
    slo: object = None       # int64[S, N, W_FIELDS] per-shard blocks
    prov: object = None
    slo_merged: object = None  # int64[N, W_FIELDS] cluster-wide block
    flight: object = None    # stacked per-shard flight rings
    press: object = None     # int64[S, PRESS_FIELDS] per-shard
    #                          mid-epoch pressure PEAKS over the chunk
    #                          (with_pressure chunks; max over epochs
    #                          of the post-ingest pre-serve probe --
    #                          the controller's migrate signal, exact
    #                          across both legs because down epochs
    #                          contribute zeros in each)


# eval_shape'd neutral epoch results for the host chaos replay's DOWN
# epochs, keyed by the static epoch configuration + state shape (the
# module-jit-cache convention; eval_shape traces, so it is not free)
_NEUTRAL_EPOCH_CACHE: dict = {}

# one jitted mid-epoch pressure probe for the host replay leg --
# integer-only reads, so the standalone launch is bit-identical to the
# fused chunk's in-scan probe
_PRESSURE_PROBE_JIT: list = []


def _pressure_probe():
    if not _PRESSURE_PROBE_JIT:
        import jax

        from ..obs import provenance as obsprov

        _PRESSURE_PROBE_JIT.append(jax.jit(obsprov.pressure_vec))
    return _PRESSURE_PROBE_JIT[0]


def neutral_epoch_view(engine: str, state_slice, m: int, kw: dict,
                       fault_met=None):
    """The committed-nothing epoch result of a DOWN shard, host-built:
    guard vectors True, slots -1, every count/cost/class 0, metrics =
    the epoch's fault-event delta -- byte-identical (dtype + shape +
    values) to slicing ``parallel.mesh.mask_epoch_outs``'s device
    masks, which is what makes the host chaos replay digest-equal to
    the fused chaos chunk.  Shapes come from ``jax.eval_shape`` of the
    same epoch program the chunk traces (nothing runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engine import fastpath
    from ..engine import stream as stream_mod

    key = (engine, m, tuple(sorted(kw.items())),
           int(state_slice.capacity), int(state_slice.ring_capacity))
    if key not in _NEUTRAL_EPOCH_CACHE:
        fn = fastpath.epoch_scan_fn(engine)
        shapes = jax.eval_shape(
            lambda st: fn(st, jnp.int64(0), m=m, **kw), state_slice)
        fields = {}
        for name in stream_mod.STREAM_OUT_FIELDS[engine]:
            sd = getattr(shapes, name)
            if name in ("guards_ok", "progress_ok"):
                arr = np.ones(sd.shape, dtype=sd.dtype)
            elif name == "slot":
                arr = np.full(sd.shape, -1, dtype=sd.dtype)
            else:
                arr = np.zeros(sd.shape, dtype=sd.dtype)
            arr.setflags(write=False)
            fields[name] = arr
        msd = shapes.metrics
        _NEUTRAL_EPOCH_CACHE[key] = (fields, msd.shape,
                                     np.dtype(msd.dtype))
    fields, mshape, mdtype = _NEUTRAL_EPOCH_CACHE[key]
    metrics = np.zeros(mshape, dtype=mdtype)
    if fault_met is not None:
        metrics += np.asarray(fault_met, dtype=mdtype)
    cls = {"prefix": fastpath.PrefixEpoch,
           "chain": fastpath.ChainEpoch,
           "calendar": fastpath.CalendarEpoch}[engine]
    return cls(state=None, metrics=metrics, **fields)


def _fault_met_vec(dropout: bool, restart: bool, perturb: int):
    """Host numpy twin of the fused chunk's per-epoch fault metric
    delta (rows 9-11 of the obs vector)."""
    import numpy as np

    from ..obs import device as obsdev

    v = np.zeros(obsdev.NUM_METRICS, dtype=np.int64)
    v[obsdev.MET_SERVER_DROPOUTS] = int(dropout)
    v[obsdev.MET_TRACKER_RESYNCS] = int(restart)
    v[obsdev.MET_FAULTS_INJECTED] = \
        int(dropout) + int(restart) + int(perturb)
    return v


def run_mesh_chunk_guarded(state, cd, cr, view_d, view_r,
                           epoch0: int, counts, *, mesh,
                           engine: str, epochs: int, m: int,
                           k: int = 0, chain_depth: int = 4,
                           dt_epoch_ns: int, waves: int,
                           anticipation_ns: int = 0,
                           allow_limit_break: bool = False,
                           with_metrics: bool = True,
                           select_impl: str = "sort",
                           tag_width: int = 64,
                           window_m: Optional[int] = None,
                           calendar_impl: str = "minstop",
                           ladder_levels: int = 8,
                           wheel_kernel: str = "xla",
                           counter_sync_every: int = 1,
                           collective_skipping: Optional[bool] = None,
                           with_pressure: bool = False,
                           hists=None, ledger=None, slo=None,
                           prov=None, flight=None, faults=None,
                           retries: int = 3, base_s: float = 0.05,
                           sleep: Callable[[float], None] =
                           _time.sleep,
                           on_retry=None, tracer=None) -> MeshGuarded:
    """Run one fused mesh chunk (``parallel.mesh``) under the
    guarded-commit contract at MESH-CHUNK granularity: bounded retry
    around the single launch, and -- on a guard trip ANYWHERE in the
    chunk, on any shard -- the whole chunk is discarded and its epochs
    replay EPOCH-MAJOR, SHARD-MINOR on the proven host robust loop
    (:func:`mesh_chunk_host_replay`: ``run_epoch_guarded`` per shard
    per epoch, with the counter-view psum recomputed on the host at
    each global sync boundary), which reproduces the fused program's
    lockstep sync semantics exactly: epoch e's views on every shard
    read the cluster counters as of the end of epoch e-1.  ``slo``
    must always be a window block (the counter plane diffs it);
    ``counts`` is ``int32[S, E, N]`` raw draws or None for serve-only
    chunks.

    ``faults`` (a ``robust.faults.FaultChunk`` or None) compiles the
    PR-3 fault model into the launch (``parallel.mesh`` documents the
    in-chunk semantics); the guard-trip fallback replays the SAME
    fault schedule on the host robust loop, so a chaos chunk degrades
    to the proven path without ever dropping the plan.  ``flight`` is
    the stacked per-shard flight-ring state (or None).

    ``collective_skipping=None`` resolves PER CHUNK from the host-side
    ``epoch0``: the grouped (collective-free non-sync epochs) program
    is picked only when the chunk is fault-free, ``epochs`` divides by
    ``counter_sync_every`` > 1, AND ``epoch0`` lands on the sync grid
    -- the alignment ``parallel.mesh.build_mesh_chunk`` documents as
    the bit-identity condition.  Off-grid chunks run the flat program
    (bit-identity over raw launch count)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..engine import stream as stream_mod
    from ..obs import slo as obsslo
    from ..obs import spans as _spans
    from ..parallel import mesh as mesh_mod

    epochs = int(epochs)
    do_ingest = counts is not None
    n_shards = int(np.asarray(jax.device_get(cd)).shape[0])
    # normalize EVERY sharded input onto the servers mesh axis before
    # the launch: entry state arrives from three sources (fresh init,
    # checkpoint restore, a previous chunk's host fallback restack)
    # with three different placements, and a compiled mesh executable
    # called with a mismatched input sharding either errors or forces
    # a silent recompile (phantom retraces in the capacity plane)
    from jax.sharding import NamedSharding, PartitionSpec as _P

    sharding = NamedSharding(mesh, _P(mesh_mod.SERVER_AXIS))

    def put(tree):
        return None if tree is None else jax.tree.map(
            lambda a: jax.device_put(a, sharding), tree)

    if slo is None:
        # the counter plane diffs the window block's delivered
        # columns, so a block must ride even when the caller runs the
        # SLO plane off -- build the throwaway here (chunk-local:
        # only cd/cr persist) instead of trapping the caller with a
        # default that crashes mid-trace
        n = int(np.asarray(jax.device_get(cd)).shape[1])
        slo = mesh_mod.stack_shards(obsslo.window_zero(n), n_shards)
    state, cd, cr, view_d, view_r = (put(x) for x in
                                     (state, cd, cr, view_d, view_r))
    hists, ledger, slo, prov, flight = (put(x) for x in
                                        (hists, ledger, slo, prov,
                                         flight))
    faults_dev = None
    if faults is not None:
        faults_dev = tuple(
            jax.device_put(jnp.asarray(a), sharding) for a in faults)
    every = max(int(counter_sync_every), 1)
    if collective_skipping is None:
        collective_skipping = (faults is None and every > 1
                               and epochs % every == 0
                               and int(epoch0) % every == 0)
    fn = mesh_mod.jit_mesh_chunk(
        mesh, engine=engine, epochs=epochs, m=m, k=k,
        chain_depth=chain_depth, dt_epoch_ns=dt_epoch_ns, waves=waves,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel,
        counter_sync_every=counter_sync_every,
        collective_skipping=collective_skipping, ingest=do_ingest,
        with_faults=faults is not None,
        with_flight=flight is not None,
        with_pressure=with_pressure)
    retry_count = [0]

    def count_retry(attempt, exc):
        retry_count[0] += 1
        _spans.instant(tracer, "mesh.retry", "retry",
                       error=type(exc).__name__)
        if on_retry is not None:
            on_retry(attempt, exc)

    counts_dev = None if counts is None \
        else jax.device_put(jnp.asarray(counts, dtype=jnp.int32),
                            sharding)

    def one():
        with _spans.span(tracer, "mesh.dispatch", "dispatch",
                         engine=engine, epochs=epochs,
                         shards=n_shards, chaos=faults is not None):
            out = fn(state, cd, cr, view_d, view_r,
                     jnp.int64(epoch0), counts_dev, hists, ledger,
                     slo, prov, flight, faults_dev)
        with _spans.span(tracer, "mesh.device_wait",
                         "device_compute"):
            return jax.block_until_ready(out)

    out = retry_with_backoff(one, retries=retries, base_s=base_s,
                             sleep=sleep, on_retry=count_retry)

    guard_field = stream_mod.STREAM_GUARD_FIELD[engine]
    guards = np.asarray(jax.device_get(out.outs[guard_field]))
    if bool(guards.all()):
        fetched = jax.device_get(out.outs)
        press = None
        if with_pressure:
            # per-shard chunk PEAKS: max over the epoch axis of the
            # mid-epoch probe rows (down epochs read zeros -- a no-op
            # under max on the nonneg fields)
            press = np.asarray(fetched["pressure"],
                               dtype=np.int64).max(axis=1)
        return MeshGuarded(
            state=out.state, cd=out.cd, cr=out.cr,
            view_d=out.view_d, view_r=out.view_r,
            epochs=tuple(
                mesh_mod.mesh_epoch_results(engine, fetched, i)
                for i in range(epochs)),
            counts=tuple(
                mesh_mod.mesh_epoch_decisions(engine, fetched, i)
                for i in range(epochs)),
            guard_trips=(0,) * epochs, mesh_fallback=0,
            retries=retry_count[0], hists=out.hists,
            ledger=out.ledger, slo=out.slo, prov=out.prov,
            slo_merged=out.slo_merged, flight=out.flight,
            press=press)

    # a guard tripped somewhere in the mesh chunk: discard it (the
    # entry state/counters are never donated) and replay epoch-major
    # on the host robust loop -- under a fault plan this is the
    # proven DEGRADED path (the supervisor counts it as a
    # mesh_chaos_fallback), and the replay carries the identical
    # fault schedule
    _spans.instant(tracer, "mesh.fallback", "retry", engine=engine,
                   epochs=epochs, shards=n_shards,
                   chaos=faults is not None)
    return mesh_chunk_host_replay(
        state, cd, cr, view_d, view_r, epoch0, counts_dev,
        engine=engine, epochs=epochs, m=m, k=k,
        chain_depth=chain_depth, dt_epoch_ns=dt_epoch_ns,
        waves=waves, anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel,
        counter_sync_every=counter_sync_every,
        with_pressure=with_pressure,
        hists=hists, ledger=ledger, slo=slo, prov=prov,
        flight=flight, faults=faults, retries=retries,
        base_s=base_s, sleep=sleep, on_retry=on_retry,
        tracer=tracer, _retries_so_far=retry_count[0])


def mesh_chunk_host_replay(state, cd, cr, view_d, view_r,
                           epoch0: int, counts, *,
                           engine: str, epochs: int, m: int,
                           k: int = 0, chain_depth: int = 4,
                           dt_epoch_ns: int, waves: int,
                           anticipation_ns: int = 0,
                           allow_limit_break: bool = False,
                           with_metrics: bool = True,
                           select_impl: str = "sort",
                           tag_width: int = 64,
                           window_m: Optional[int] = None,
                           calendar_impl: str = "minstop",
                           ladder_levels: int = 8,
                           wheel_kernel: str = "xla",
                           counter_sync_every: int = 1,
                           with_pressure: bool = False,
                           hists=None, ledger=None, slo=None,
                           prov=None, flight=None, faults=None,
                           retries: int = 3, base_s: float = 0.05,
                           sleep: Callable[[float], None] =
                           _time.sleep,
                           on_retry=None, tracer=None,
                           _retries_so_far: int = 0) -> MeshGuarded:
    """The HOST ROBUST LOOP: drive one mesh chunk's epochs epoch-major
    shard-minor on the proven per-epoch path, with the counter-view
    psum recomputed as a host sum at the same global sync grid and --
    when ``faults`` is given -- the exact in-chunk fault semantics of
    ``parallel.mesh.build_mesh_chunk``: a down shard runs nothing and
    contributes a :func:`neutral_epoch_view` row, its state/telemetry
    /counters frozen; restarts re-sync the held views off-grid; dup
    doubles the completion fold; skew lenses the shard's clock; fault
    events patch the epoch's metrics rows.

    This is both the guard-trip fallback of
    :func:`run_mesh_chunk_guarded` AND the digest reference the chaos
    gates compare the fused chunk against (tests/test_mesh.py,
    scripts/ci.sh mesh chaos smoke): a seeded chaos chunk must be
    decision-for-decision and counter-view-for-counter-view identical
    to this loop under the same plan.  ``slo`` must be a window block
    (stacked [S, N, W_FIELDS]); ``counts`` is ``int32[S, E, N]`` or
    None."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..engine import fastpath
    from ..engine import stream as stream_mod
    from ..obs import slo as obsslo
    from ..parallel.tracker import global_counters_from

    epochs = int(epochs)
    do_ingest = counts is not None
    n_shards = int(np.asarray(jax.device_get(cd)).shape[0])
    if slo is None:
        # the counter plane diffs the window block's delivered
        # columns; when the caller runs the SLO plane off, ride a
        # throwaway zero block (chunk-local -- only cd/cr persist),
        # exactly like run_mesh_chunk_guarded's fused leg
        from ..parallel import mesh as mesh_mod
        n = int(np.asarray(jax.device_get(cd)).shape[1])
        slo = mesh_mod.stack_shards(obsslo.window_zero(n), n_shards)
    every = max(int(counter_sync_every), 1)
    retry_count = [_retries_so_far]
    ingest_step = stream_mod.jit_ingest_step(
        dt_epoch_ns=dt_epoch_ns, waves=waves) if do_ingest else None
    dev0 = jax.devices()[0]

    def slic(tree, s):
        # per-shard slices re-placed on ONE device: the round-path
        # epoch executables are compiled for single-device inputs,
        # and a slice still committed to the mesh would reject them
        return None if tree is None \
            else jax.tree.map(lambda a: jax.device_put(a[s], dev0),
                              tree)

    sts = [slic(state, s) for s in range(n_shards)]
    cur = {name: [slic(acc, s) for s in range(n_shards)]
           for name, acc in (("hists", hists), ("ledger", ledger),
                             ("slo", slo), ("prov", prov),
                             ("flight", flight))}
    cd_np = np.asarray(jax.device_get(cd), dtype=np.int64).copy()
    cr_np = np.asarray(jax.device_get(cr), dtype=np.int64).copy()
    vd_np = np.asarray(jax.device_get(view_d), dtype=np.int64).copy()
    vr_np = np.asarray(jax.device_get(view_r), dtype=np.int64).copy()
    if faults is not None:
        f_up = np.asarray(faults[0], dtype=bool)
        f_skew = np.asarray(faults[1], dtype=np.int64)
        f_delay = np.asarray(faults[2], dtype=bool)
        f_dup = np.asarray(faults[3], dtype=bool)
        up_prev = np.asarray(faults[4], dtype=bool).copy()
    neutral_kw = fastpath.epoch_scan_kwargs(
        engine, k=k, chain_depth=chain_depth, select_impl=select_impl,
        tag_width=tag_width, window_m=window_m,
        calendar_impl=calendar_impl, ladder_levels=ladder_levels,
        wheel_kernel=wheel_kernel,
        anticipation_ns=anticipation_ns,
        allow_limit_break=allow_limit_break,
        with_metrics=with_metrics)
    press_np = None
    if with_pressure:
        from ..obs import provenance as obsprov
        press_np = np.zeros((n_shards, obsprov.PRESS_FIELDS),
                            dtype=np.int64)
    ep_rows, count_rows, trip_rows = [], [], []
    for i in range(epochs):
        t_base = (int(epoch0) + i) * int(dt_epoch_ns)
        sync = (int(epoch0) + i) % every == 0
        # the epoch-entry psum, from the counters as of the end of
        # epoch i-1 (the fused program's lockstep semantics); under a
        # plan each shard refreshes only per its own masks below.
        # Only reduced when some shard CAN refresh this epoch -- a
        # sync epoch, or an off-grid restart -- so a plain fallback
        # replay at K>1 skips the O(S*N) host sum on non-sync epochs
        may_refresh = sync or (
            faults is not None and bool((f_up[:, i] & ~up_prev).any()))
        g_d = g_r = None
        if may_refresh:
            g_d, g_r = global_counters_from(
                cd_np, cr_np, lambda x: x.sum(axis=0))
        row, n_dec, trips = [], 0, 0
        for s in range(n_shards):
            if faults is not None:
                up = bool(f_up[s, i])
                skew = int(f_skew[s, i])
                delay = bool(f_delay[s, i])
                dup = bool(f_dup[s, i])
                restart = up and not up_prev[s]
                dropout = (not up) and up_prev[s]
                refresh = (sync and up and not delay) or restart
                perturb = (int(dup and up) + int(delay and up)
                           + int(skew != 0 and up))
            else:
                up, skew, dup = True, 0, False
                restart = dropout = False
                perturb = 0
                refresh = sync
            if refresh:
                vd_np[s] = g_d
                vr_np[s] = g_r
            if not up:
                # the shard is DOWN this epoch: nothing runs, nothing
                # commits (arrivals posted to it are lost), its row
                # reads the committed-nothing neutrals + fault rows
                row.append((neutral_epoch_view(
                    engine, sts[s], m, neutral_kw,
                    _fault_met_vec(dropout, restart, perturb)),))
                continue
            if ingest_step is not None:
                # the raw-draw slice is still committed to the whole
                # mesh; the single-device round path needs it local
                sts[s] = ingest_step(
                    sts[s],
                    jax.device_put(counts[s, i], dev0),
                    jnp.int64(t_base + skew))
            if press_np is not None:
                # the fused chunk's mid-epoch probe: post-ingest,
                # pre-serve, at the shard's (skew-lensed) serve time
                press_np[s] = np.maximum(press_np[s], np.asarray(
                    jax.device_get(_pressure_probe()(
                        sts[s],
                        jnp.int64(t_base + skew + int(dt_epoch_ns)))),
                    dtype=np.int64))
            w_prev = np.asarray(jax.device_get(cur["slo"][s]),
                                dtype=np.int64)
            ep = run_epoch_guarded(
                sts[s], t_base + int(dt_epoch_ns), engine=engine,
                m=m, k=k, chain_depth=chain_depth,
                anticipation_ns=anticipation_ns,
                allow_limit_break=allow_limit_break,
                with_metrics=with_metrics, select_impl=select_impl,
                tag_width=tag_width, window_m=window_m,
                calendar_impl=calendar_impl,
                ladder_levels=ladder_levels,
                wheel_kernel=wheel_kernel, skew_ns=skew,
                hists=cur["hists"][s], ledger=cur["ledger"][s],
                flight=cur["flight"][s],
                slo=cur["slo"][s], prov=cur["prov"][s],
                retries=retries, base_s=base_s, sleep=sleep,
                on_retry=on_retry, tracer=tracer)
            sts[s] = ep.state
            for name in ("hists", "ledger", "slo", "prov", "flight"):
                if cur[name][s] is not None:
                    cur[name][s] = getattr(ep, name)
            w_now = np.asarray(jax.device_get(ep.slo),
                               dtype=np.int64)
            mult = 2 if dup else 1
            cd_np[s] += (w_now[:, obsslo.W_OPS]
                         - w_prev[:, obsslo.W_OPS]) * mult
            cr_np[s] += (w_now[:, obsslo.W_RESV_OPS]
                         - w_prev[:, obsslo.W_RESV_OPS]) * mult
            retry_count[0] += ep.retries
            results = ep.results
            if restart or perturb:
                # the fused chunk folds the epoch's fault-event delta
                # into its metrics row; patch the first result so the
                # host loop's metric totals match the oracle exactly
                fv = _fault_met_vec(False, restart, perturb)
                r0 = results[0]
                results = (r0._replace(
                    metrics=r0.metrics + jnp.asarray(fv)),) \
                    + results[1:]
            row.append(tuple(results))
            n_dec += ep.count
            trips += ep.rebase_fallbacks + ep.serial_fallbacks
        if faults is not None:
            up_prev = f_up[:, i].copy()
        ep_rows.append(tuple(row))
        count_rows.append(n_dec)
        trip_rows.append(trips)

    def restack(parts):
        if any(p is None for p in parts):
            return None
        return jax.tree.map(lambda *xs: jnp.stack(xs), *parts)

    slo_stacked = restack(cur["slo"])
    return MeshGuarded(
        state=restack(sts), cd=jnp.asarray(cd_np),
        cr=jnp.asarray(cr_np), view_d=jnp.asarray(vd_np),
        view_r=jnp.asarray(vr_np), epochs=tuple(ep_rows),
        counts=tuple(count_rows), guard_trips=tuple(trip_rows),
        mesh_fallback=1, retries=retry_count[0],
        hists=restack(cur["hists"]), ledger=restack(cur["ledger"]),
        slo=slo_stacked, prov=restack(cur["prov"]),
        flight=restack(cur["flight"]),
        slo_merged=jnp.asarray(obsslo.window_combine_np(
            np.zeros_like(np.asarray(slo_stacked[0])),
            *np.asarray(jax.device_get(slo_stacked)))),
        press=press_np)


# ----------------------------------------------------------------------
# escalation / degradation ladder (docs/ROBUSTNESS.md)
# ----------------------------------------------------------------------

# Rung order is cheapest-concession-first: each (knob, fast, safe)
# step trades a fast path for its always-exact twin, and every rung is
# already pinned bit-identical/exact by the differential suites
# (tests/test_calendar_wheel.py, tests/test_calendar_bucketed.py,
# tests/test_radix.py), so a degraded run is SLOWER, never DIVERGENT.
# The two calendar rungs share a knob and CHAIN: wheel steps down to
# bucketed first, and a second concession carries bucketed to minstop
# -- rung engagement is keyed by (knob, fast), not knob alone.
LADDER_RUNGS = (
    ("calendar_impl", "wheel", "bucketed"),
    ("calendar_impl", "bucketed", "minstop"),
    ("select_impl", "radix", "sort"),
    ("tag_width", 32, 64),
)


class LadderStep(NamedTuple):
    """One recorded step-down."""

    knob: str
    from_value: object
    to_value: object
    reason: str     # "guard_trips" | "launch_failures" | "resumed"


class DegradationLadder:
    """Escalation policy over the guarded-commit contract: when an
    epoch loop keeps tripping guards or exhausting launch retries for
    ``threshold`` consecutive epochs, step down ONE rung of
    :data:`LADDER_RUNGS` (the first still engaged in the caller's
    config) and keep serving.  Disabled (``enabled=False``) it is
    inert: ``apply`` is the identity and ``note_epoch`` never steps --
    the zero-cost-when-off gate pins a disabled ladder's obs row at 0.

    The engaged-rung set is tiny host state; :meth:`encode` /
    :meth:`load` round-trip it through an int64 vector so the
    supervisor can carry ladder position inside its rotation
    checkpoints (a resumed run must keep serving at the same degraded
    operating point, or the replay would diverge from the
    uninterrupted run)."""

    def __init__(self, enabled: bool = True, threshold: int = 2,
                 tracer=None):
        self.enabled = bool(enabled)
        self.threshold = max(int(threshold), 1)
        self.steps: list = []       # LadderStep, in engagement order
        self._consecutive = 0
        # optional obs.spans.SpanTracer: step-downs record a "retry"
        # instant so the timeline shows WHEN the run degraded
        self.tracer = tracer

    @property
    def steps_taken(self) -> int:
        return len(self.steps)

    def _engaged(self, knob: str, fast) -> bool:
        # keyed by (knob, fast): the two calendar rungs share a knob,
        # and engaging wheel->bucketed must not imply
        # bucketed->minstop
        return any(s.knob == knob and s.from_value == fast
                   for s in self.steps)

    def apply(self, cfg: dict) -> dict:
        """Map a config through the engaged rungs (a knob already at
        its safe value is untouched).  Rung order chains the shared-
        knob calendar rungs: wheel->bucketed rewrites the value the
        bucketed->minstop rung then reads."""
        out = dict(cfg)
        for knob, fast, safe in LADDER_RUNGS:
            if self._engaged(knob, fast) and out.get(knob) == fast:
                out[knob] = safe
        return out

    def can_step(self, cfg: dict) -> bool:
        """True while a rung is still engageable for ``cfg`` -- the
        retry loops use this to bound re-attempts: a failure with
        nothing left to concede must surface, not spin."""
        return self.enabled and any(
            cfg.get(knob) == fast and not self._engaged(knob, fast)
            for knob, fast, _safe in LADDER_RUNGS)

    def note_epoch(self, cfg: dict, *, guard_trips: int = 0,
                   launch_failures: int = 0) -> int:
        """Observe one epoch's fault counters (POST-``apply`` config).
        Returns the number of step-downs taken (0 or 1); a clean epoch
        resets the consecutive-trip counter."""
        if not self.enabled:
            return 0
        if not (guard_trips or launch_failures):
            self._consecutive = 0
            return 0
        self._consecutive += 1
        if self._consecutive < self.threshold:
            return 0
        self._consecutive = 0
        for knob, fast, safe in LADDER_RUNGS:
            if cfg.get(knob) == fast and not self._engaged(knob, fast):
                reason = "guard_trips" if guard_trips \
                    else "launch_failures"
                self.steps.append(LadderStep(knob, fast, safe, reason))
                if self.tracer is not None:
                    self.tracer.instant(
                        "ladder.step", "retry", knob=knob,
                        to=str(safe), reason=reason)
                return 1
        return 0    # fully degraded already; nothing left to concede

    def describe(self) -> list:
        """JSON-able step list for run records."""
        return [{"knob": s.knob, "from": s.from_value,
                 "to": s.to_value, "reason": s.reason}
                for s in self.steps]

    # -- checkpoint round-trip (int64[R + 1]: engaged flags + counter)
    def encode(self):
        import numpy as np
        vec = [1 if self._engaged(knob, fast) else 0
               for knob, fast, _ in LADDER_RUNGS]
        return np.asarray(vec + [self._consecutive], dtype=np.int64)

    def load(self, vec) -> None:
        import numpy as np
        vec = np.asarray(vec, dtype=np.int64)
        assert vec.shape == (len(LADDER_RUNGS) + 1,), vec.shape
        self.steps = [LadderStep(knob, fast, safe, "resumed")
                      for flag, (knob, fast, safe)
                      in zip(vec[:-1], LADDER_RUNGS) if flag]
        self._consecutive = int(vec[-1])
