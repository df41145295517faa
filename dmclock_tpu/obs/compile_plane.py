"""Compile/retrace observatory: the capacity plane's time axis.

Every hot launch path in the repo routes through a MODULE-LEVEL jit
cache (the ``engine/queue.py`` ``_JIT_CACHE`` convention), because a
re-trace costs seconds of host time and a TPU re-compile of the big
fused programs costs tens of seconds to minutes (PROFILE.md) --
a retrace STORM is a silicon-session-killing failure mode that today
is invisible until the wall clock is already gone.  This module makes
every one of those caches observable:

- :func:`instrumented_jit` wraps ``jax.jit`` for a cache entry.  It
  keeps a per-argument-signature map of AOT-compiled executables
  (``fn.lower(...).compile()``), so the FIRST call for each signature
  is where lowering and compilation happen -- timed separately,
  recorded per entry, and attributed: a second signature arriving at
  an existing entry is a **retrace**, recorded together with the
  leaf-level arg-signature diff that caused it.
- Each compile also captures ``Compiled.cost_analysis()`` (flops /
  bytes accessed -- the roofline attributor's numerator) and
  ``Compiled.memory_analysis()`` (argument / output / temp /
  generated-code HBM bytes -- what the static ledger in
  ``obs.capacity`` is validated against).  Both are advisory on
  XLA:CPU (PROFILE.md); the TPU session is the real record.
- Records export three ways: ``plane().snapshot()`` (JSON-able),
  ``publish_compile_metrics`` (``dmclock_compile_*`` Prometheus
  families), and -- when a tracer is attached via ``set_tracer`` --
  one ``compile``-category span per lower+compile into the PR-7 span
  stream, so compile time lands on the same timeline as the launches
  it delays and rides the supervisor's ``span_log`` checkpoint-
  boundary flush (the rotation checkpoints' durability window).

**The plane cannot perturb a decision**: the wrapped executable is the
exact program ``jax.jit`` would have dispatched (same trace, same
donation), and with the plane disabled (``enable(False)`` or
``DMCLOCK_COMPILE_PLANE=0``) calls route through the plain ``jax.jit``
path untouched.  Decisions are bit-identical either way (ci.sh
capacity smoke).  If a compiled executable rejects a call our
signature considered equal (an aval aspect the signature cannot see,
e.g. an exotic sharding), the wrapper permanently routes that
signature through the plain jit path and counts the miss -- telemetry
must never kill the launch it observes.
"""

from __future__ import annotations

import os
import threading
import time as _walltime
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .spans import span as _span

# every live InstrumentedJit, so clear_compiled() can drop the held
# executables alongside jax.clear_caches() (the test suite's
# between-modules compile-state relief must reach them too)
_ALL_WRAPPERS: "weakref.WeakSet" = weakref.WeakSet()


def clear_compiled() -> None:
    """Drop every wrapper's held AOT executables (records are kept).
    Call next to ``jax.clear_caches()`` when shedding compile state --
    the next call per signature re-lowers and re-compiles, recorded as
    a retrace."""
    for w in list(_ALL_WRAPPERS):
        w.clear_compiled()


def live_executables(cache: str) -> list:
    """The AOT executables the wrappers of one cache family hold --
    the programs that actually ran, for checks on their HLO (e.g. that
    a Pallas kernel is in it: ``tpu_custom_call``)."""
    return [c for w in list(_ALL_WRAPPERS) if w._cache == cache
            for c in list(w._compiled.values()) if c is not _DISPATCH]


# one retrace event ring entry per (re)trace, what the watchdog's
# retrace-storm check windows over
_RETRACE_RING = 1024
# how many leaf-level diffs a retrace record keeps (arg pytrees can
# have hundreds of leaves; the first few changed ones name the cause)
_DIFF_LIMIT = 8
_ENTRY_STR_LIMIT = 160


def _entry_str(entry: Any) -> str:
    s = repr(entry)
    return s if len(s) <= _ENTRY_STR_LIMIT else \
        s[:_ENTRY_STR_LIMIT - 3] + "..."


_PY_SCALARS = (bool, int, float, complex)


def _leaf_spec(leaf):
    """Hashable per-leaf signature matching jax's retrace rule closely
    enough: arrays key by (shape, dtype, weak_type) -- values never
    retrace; python scalars key by TYPE only (jax traces them weakly,
    so 3 and 4 share one executable); anything else by repr.  Dtype
    OBJECTS, not strings -- str(dtype) per leaf per call was the
    dominant per-call cost."""
    if isinstance(leaf, _PY_SCALARS):
        return type(leaf)
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), dtype,
                getattr(leaf, "weak_type", False))
    return ("obj", repr(leaf))


def _leaf_spec_readable(leaf) -> tuple:
    """The human-facing form for retrace diffs (compile-time only)."""
    if isinstance(leaf, _PY_SCALARS):
        return ("py", type(leaf).__name__)
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return ("arr", tuple(shape), str(dtype),
                bool(getattr(leaf, "weak_type", False)))
    return ("obj", repr(leaf))


def _signature(args, kwargs) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_spec(x) for x in leaves))


def _signature_or_none(args, kwargs):
    """One pass over the flattened args: the hashable signature, or
    None when a leaf is a tracer (this jit is inlining inside an outer
    trace -- route to the plain jit path)."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    tr = jax.core.Tracer
    specs = []
    for leaf in leaves:
        if isinstance(leaf, tr):
            return None
        specs.append(_leaf_spec(leaf))
    return (treedef, tuple(specs))


def _path_specs(args, kwargs) -> Dict[str, tuple]:
    """Leaf path -> spec, for the retrace diff (computed only when a
    compile actually happens -- never on the per-call hot path)."""
    out = {}
    try:
        flat, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
        for path, leaf in flat:
            out[jax.tree_util.keystr(path)] = \
                _leaf_spec_readable(leaf)
    except Exception:      # ancient jax without path flattening
        leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
        for i, leaf in enumerate(leaves):
            out[f"[{i}]"] = _leaf_spec_readable(leaf)
    return out


def _sig_diff(old: Dict[str, tuple], new: Dict[str, tuple]
              ) -> List[str]:
    """Human-readable leaf diffs between two path-spec maps: exactly
    what changed shape/dtype/type to cause the retrace."""
    diffs = []
    for path in new:
        if path not in old:
            diffs.append(f"{path}: added {new[path]}")
        elif old[path] != new[path]:
            diffs.append(f"{path}: {old[path]} -> {new[path]}")
    for path in old:
        if path not in new:
            diffs.append(f"{path}: removed (was {old[path]})")
    return diffs[:_DIFF_LIMIT]


def normalize_cost_analysis(ca) -> Dict[str, float]:
    """ONE normalization of a raw ``cost_analysis()`` value
    (list-of-dicts on some backends, dict on others) -- shared by the
    plane's records and ``bench.epoch_cost_analysis`` so the bench row
    and the compile record can never disagree on the same program."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    out = {}
    for key in ("flops", "bytes accessed", "transcendentals"):
        if key in (ca or {}):
            out[key.replace(" ", "_")] = float(ca[key])
    return out


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """Normalized flops/bytes from ``Compiled.cost_analysis()`` --
    degrade to empty, never raise (callers that want the error note
    catch around ``compiled.cost_analysis()`` themselves and
    normalize with :func:`normalize_cost_analysis`)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return normalize_cost_analysis(ca)


def memory_analysis_dict(compiled) -> Dict[str, int]:
    """The HBM footprint breakdown from
    ``Compiled.memory_analysis()``: argument / output / temp /
    generated-code / aliased bytes.  ``total_bytes`` is the resident
    peak estimate (alias overlap -- donated outputs sharing argument
    buffers -- subtracted once).  Empty when the backend cannot
    report."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for name, key in (("argument_size_in_bytes", "argument_bytes"),
                      ("output_size_in_bytes", "output_bytes"),
                      ("temp_size_in_bytes", "temp_bytes"),
                      ("generated_code_size_in_bytes", "code_bytes"),
                      ("alias_size_in_bytes", "alias_bytes")):
        v = getattr(ma, name, None)
        if v is not None:
            out[key] = int(v)
    if out:
        out["total_bytes"] = (out.get("argument_bytes", 0)
                              + out.get("output_bytes", 0)
                              + out.get("temp_bytes", 0)
                              + out.get("code_bytes", 0)
                              - out.get("alias_bytes", 0))
    return out


class _EntryStats:
    """Aggregate compile record of ONE cache entry (one static
    configuration): how many times it lowered+compiled, how long that
    took, what the latest executable's cost/memory analyses said, and
    the signature diff behind the most recent retrace."""

    __slots__ = ("cache", "entry", "compiles", "retraces",
                 "lower_ns", "compile_ns", "cost", "hbm",
                 "path_specs", "last_diff", "dispatch_fallbacks")

    def __init__(self, cache: str, entry: str):
        self.cache = cache
        self.entry = entry
        self.compiles = 0
        self.retraces = 0
        self.lower_ns = 0
        self.compile_ns = 0
        self.cost: Dict[str, float] = {}
        self.hbm: Dict[str, int] = {}
        self.path_specs: Optional[Dict[str, tuple]] = None
        self.last_diff: List[str] = []
        self.dispatch_fallbacks = 0

    def to_dict(self) -> dict:
        return {"cache": self.cache, "entry": self.entry,
                "compiles": self.compiles, "retraces": self.retraces,
                "lower_ms": self.lower_ns / 1e6,
                "compile_ms": self.compile_ns / 1e6,
                "cost_analysis": dict(self.cost),
                "memory_analysis": dict(self.hbm),
                "last_retrace_diff": list(self.last_diff),
                "dispatch_fallbacks": self.dispatch_fallbacks}


class CompilePlane:
    """Process-wide compile/retrace ledger.  ``clock_ns`` is
    injectable for deterministic watchdog tests (same clock domain as
    the watchdog's)."""

    def __init__(self, clock_ns: Callable[[], int] =
                 _walltime.perf_counter_ns):
        self._mtx = threading.Lock()
        self.clock_ns = clock_ns
        self.enabled = os.environ.get(
            "DMCLOCK_COMPILE_PLANE", "1").lower() not in (
                "0", "off", "false")
        self._tracer_ref = None     # weakref to a SpanTracer, or None
        self._entries: Dict[Tuple[str, str], _EntryStats] = {}
        self._retraces: deque = deque(maxlen=_RETRACE_RING)

    # -- control -------------------------------------------------------
    def enable(self, on: bool) -> "CompilePlane":
        self.enabled = bool(on)
        return self

    def set_tracer(self, tracer) -> None:
        """Route future compiles into ``tracer`` as ``compile``-category
        spans (the PR-7 span stream; None detaches).  Held WEAKLY: the
        plane is process-global while tracers are per-incarnation
        (supervisor) or per-run (bench), and a strong reference would
        pin a dead job's tracer -- and its span ring -- forever, with
        later compiles appended to a stream nobody drains."""
        self._tracer_ref = None if tracer is None \
            else weakref.ref(tracer)

    @property
    def tracer(self):
        if self._tracer_ref is None:
            return None
        return self._tracer_ref()   # None once the owner dropped it

    def reset(self) -> None:
        with self._mtx:
            self._entries.clear()
            self._retraces.clear()

    # -- recording -----------------------------------------------------
    def _entry(self, cache: str, entry: str) -> _EntryStats:
        key = (cache, entry)
        e = self._entries.get(key)
        if e is None:
            e = self._entries[key] = _EntryStats(cache, entry)
        return e

    def record_compile(self, cache: str, entry: str, *,
                       lower_ns: int, compile_ns: int,
                       cost: Dict[str, float], hbm: Dict[str, int],
                       path_specs: Optional[Dict[str, tuple]] = None
                       ) -> dict:
        """Fold one lower+compile into the entry's record; returns the
        span-args payload (retrace flag + diff included) so the caller
        can attach it to the compile span it just closed."""
        with self._mtx:
            e = self._entry(cache, entry)
            retrace = e.compiles > 0
            diff: List[str] = []
            if retrace:
                e.retraces += 1
                if e.path_specs is not None and path_specs is not None:
                    diff = _sig_diff(e.path_specs, path_specs)
                e.last_diff = diff
                self._retraces.append((self.clock_ns(),
                                       f"{cache}:{entry}"))
            e.compiles += 1
            e.lower_ns += int(lower_ns)
            e.compile_ns += int(compile_ns)
            if cost:
                e.cost = dict(cost)
            if hbm:
                e.hbm = dict(hbm)
            if path_specs is not None:
                e.path_specs = path_specs
        out = {"cache": cache, "entry": entry, "retrace": retrace,
               "lower_ms": lower_ns / 1e6, "compile_ms": compile_ns / 1e6}
        if cost.get("flops") is not None:
            out["flops"] = cost["flops"]
        if cost.get("bytes_accessed") is not None:
            out["bytes_accessed"] = cost["bytes_accessed"]
        if hbm.get("total_bytes") is not None:
            out["hbm_total_bytes"] = hbm["total_bytes"]
        if diff:
            out["sig_diff"] = diff
        return out

    def note_dispatch_fallback(self, cache: str, entry: str) -> None:
        with self._mtx:
            self._entry(cache, entry).dispatch_fallbacks += 1

    # -- reading -------------------------------------------------------
    def entries(self) -> List[dict]:
        with self._mtx:
            return [e.to_dict() for e in self._entries.values()]

    def totals(self) -> dict:
        with self._mtx:
            es = list(self._entries.values())
            return {
                "entries": len(es),
                "compiles": sum(e.compiles for e in es),
                "retraces": sum(e.retraces for e in es),
                "lower_ms_total": sum(e.lower_ns for e in es) / 1e6,
                "compile_ms_total":
                    sum(e.compile_ns for e in es) / 1e6,
                "dispatch_fallbacks":
                    sum(e.dispatch_fallbacks for e in es),
            }

    def snapshot(self) -> dict:
        """JSON-able full record (what ``scripts/capacity_report.py``
        and the bench JSON line derive from)."""
        return {"totals": self.totals(), "entries": self.entries()}

    def retrace_events(self) -> List[Tuple[int, str]]:
        """(clock_ns, "cache:entry") per retrace, newest-bounded --
        the watchdog's retrace-storm feed."""
        with self._mtx:
            return list(self._retraces)


_PLANE = CompilePlane()


def plane() -> CompilePlane:
    """The process-wide compile plane (module caches all record
    here)."""
    return _PLANE


def set_tracer(tracer) -> None:
    _PLANE.set_tracer(tracer)


def _timed_compile(pl: CompilePlane, cache: str, entry: str,
                   jitted, args, kwargs):
    """One timed lower+compile with full attribution: the shared leg
    of :class:`InstrumentedJit` and :func:`aot_record`."""
    with _span(pl.tracer, f"compile.{cache}", "compile"):
        t0 = pl.clock_ns()
        lowered = jitted.lower(*args, **kwargs)
        t1 = pl.clock_ns()
        compiled = lowered.compile()
        t2 = pl.clock_ns()
    rec = pl.record_compile(
        cache, entry, lower_ns=t1 - t0, compile_ns=t2 - t1,
        cost=cost_analysis_dict(compiled),
        hbm=memory_analysis_dict(compiled),
        path_specs=_path_specs(args, kwargs))
    if pl.tracer is not None:
        # one instant carrying the full record payload next to the
        # span (spans close before the record exists; the instant IS
        # the compile record on the timeline)
        pl.tracer.instant(f"compile.{cache}.record", "compile", **rec)
    return compiled


# sentinel: signatures whose AOT executable rejected a call route
# through the plain jit dispatch path forever after
_DISPATCH = object()


class InstrumentedJit:
    """``jax.jit(fn)`` plus the compile observatory.  Drop-in for the
    module jit caches: calling it dispatches the identical compiled
    program; the first call per argument signature is where lowering
    and compilation happen (timed + recorded), and a second signature
    on the same entry is recorded as a retrace with its diff."""

    __slots__ = ("_fn", "_cache", "_entry", "_jit", "_compiled",
                 "_mtx", "__weakref__")

    def __init__(self, fn, *, cache: str, entry: Any, **jit_kwargs):
        self._fn = fn
        self._cache = cache
        self._entry = _entry_str(entry)
        self._jit = jax.jit(fn, **jit_kwargs)
        self._compiled: Dict[tuple, Any] = {}
        self._mtx = threading.Lock()
        _ALL_WRAPPERS.add(self)

    def clear_compiled(self) -> None:
        with self._mtx:
            self._compiled.clear()

    def __call__(self, *args, **kwargs):
        pl = _PLANE
        if not pl.enabled:
            # plane off -> the byte-identical plain path
            return self._jit(*args, **kwargs)
        sig = _signature_or_none(args, kwargs)
        if sig is None:    # tracer args: this jit is inlining inside
            return self._jit(*args, **kwargs)   # an outer trace
        # lock-free read: dict get is GIL-atomic, writes stay locked
        comp = self._compiled.get(sig)
        if comp is None:
            with self._mtx:
                comp = self._compiled.get(sig)
                if comp is None:
                    comp = _timed_compile(pl, self._cache, self._entry,
                                          self._jit, args, kwargs)
                    self._compiled[sig] = comp
        if comp is _DISPATCH:
            return self._jit(*args, **kwargs)
        try:
            return comp(*args, **kwargs)
        except (TypeError, ValueError) as e:
            # an aval aspect the signature cannot see (layout,
            # sharding): this signature routes through plain jit
            # dispatch from now on.  TypeError covers the classic
            # aval mismatch; newer jax raises ValueError for a
            # committed-sharding mismatch (e.g. a mesh-placed array
            # calling an executable compiled for a single device --
            # the mesh fallback path's shape).  Both are raised
            # BEFORE execution/donation, so the re-dispatch is safe;
            # any OTHER ValueError surfaces unchanged.
            if isinstance(e, ValueError) and \
                    "sharding" not in str(e) and \
                    "layout" not in str(e):
                raise
            with self._mtx:
                self._compiled[sig] = _DISPATCH
            pl.note_dispatch_fallback(self._cache, self._entry)
            return self._jit(*args, **kwargs)

    # the underlying jit, for callers that need .lower() etc.
    @property
    def jitted(self):
        return self._jit


def instrumented_jit(fn, *, cache: str, entry: Any,
                     **jit_kwargs) -> InstrumentedJit:
    """The module-jit-cache building block:
    ``_CACHE[key] = instrumented_jit(fn, cache="queue", entry=key)``
    replaces ``_CACHE[key] = jax.jit(fn)`` everywhere (docs/
    OBSERVABILITY.md "Capacity plane")."""
    return InstrumentedJit(fn, cache=cache, entry=entry, **jit_kwargs)


def aot_record(cache: str, entry: Any, jitted, *args, **kwargs):
    """Timed+recorded twin of the bench's AOT discipline
    ``jax.jit(fn).lower(*args).compile()``: same Compiled handle back,
    with the lower/compile walls, cost_analysis, and memory_analysis
    folded into the plane under ``(cache, entry)``."""
    pl = _PLANE
    if not pl.enabled:
        return jitted.lower(*args, **kwargs).compile()
    return _timed_compile(pl, cache, _entry_str(entry), jitted,
                          args, kwargs)


def publish_compile_metrics(registry, pl: Optional[CompilePlane] = None
                            ) -> None:
    """Drain the plane into a registry as ``dmclock_compile_*``
    families: process totals plus per-cache-family rollups (labelled
    ``{cache=...}``; per-ENTRY labels would explode cardinality)."""
    pl = pl or _PLANE
    t = pl.totals()
    rows = (
        ("dmclock_compile_events_total", "lower+compile events "
         "recorded by the compile plane (docs/OBSERVABILITY.md "
         "capacity plane)", t["compiles"]),
        ("dmclock_compile_retraces_total", "cache entries re-traced "
         "by a changed argument signature", t["retraces"]),
        ("dmclock_compile_ms_total", "total XLA compile wall (ms)",
         t["compile_ms_total"]),
        ("dmclock_compile_lower_ms_total", "total jaxpr lowering "
         "wall (ms)", t["lower_ms_total"]),
        ("dmclock_compile_cache_entries", "live instrumented jit "
         "cache entries", t["entries"]),
    )
    for name, help_text, v in rows:
        registry.gauge(name, help_text).set(float(v))
    by_cache: Dict[str, dict] = {}
    for e in pl.entries():
        acc = by_cache.setdefault(e["cache"], {
            "compile_ms": 0.0, "retraces": 0, "flops": 0.0,
            "bytes_accessed": 0.0, "hbm_total_bytes": 0})
        acc["compile_ms"] += e["compile_ms"]
        acc["retraces"] += e["retraces"]
        acc["flops"] += e["cost_analysis"].get("flops", 0.0)
        acc["bytes_accessed"] += \
            e["cost_analysis"].get("bytes_accessed", 0.0)
        acc["hbm_total_bytes"] += \
            e["memory_analysis"].get("total_bytes", 0)
    for cache, acc in by_cache.items():
        lbl = {"cache": cache}
        registry.gauge("dmclock_compile_ms_total", "", labels=lbl) \
            .set(acc["compile_ms"])
        registry.gauge("dmclock_compile_retraces_total", "",
                       labels=lbl).set(acc["retraces"])
        registry.gauge(
            "dmclock_compile_flops", "XLA cost_analysis flops, summed "
            "over the cache family's latest executables (advisory on "
            "XLA:CPU)", labels=lbl).set(acc["flops"])
        registry.gauge(
            "dmclock_compile_bytes_accessed", "XLA cost_analysis "
            "bytes accessed (advisory on XLA:CPU)",
            labels=lbl).set(acc["bytes_accessed"])
        registry.gauge(
            "dmclock_compile_hbm_bytes", "XLA memory_analysis "
            "resident total (args+outputs+temps+code-aliased)",
            labels=lbl).set(acc["hbm_total_bytes"])
