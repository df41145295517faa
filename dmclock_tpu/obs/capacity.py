"""Static HBM footprint ledger, capacity planner, roofline attributor.

The capacity plane's space axis.  Three questions the silicon campaign
and the mesh-sharding item (ROADMAP) cannot currently answer without
burning a TPU session on an OOM:

1. **How many HBM bytes does a configuration pin?**  :func:`hbm_ledger`
   walks the live device-resident pytrees -- the ``EngineState`` client
   block + tail rings, the telemetry histograms/ledger, the flight
   ring, the SLO window block, the lifecycle slot map -- and the epoch
   program's own output blocks (derived with ``jax.eval_shape`` from
   the REAL epoch function, so the ledger cannot rot when a result
   field is added), per subsystem.
2. **How many clients fit a chip?**  Every subsystem is linear in N,
   so :func:`capacity_model` fits the exact (bytes/client, fixed
   bytes) line from two abstract evaluations and
   :func:`plan_capacity` inverts it against an HBM budget
   (:func:`device_hbm_budget` reads the attached device's
   ``memory_stats``; ``DMCLOCK_HBM_BUDGET_BYTES`` overrides, CPU
   boxes report None).  The projection is validated against
   ``Compiled.memory_analysis()`` of the real compiled epoch program
   (ci.sh capacity smoke: within 10% at the cfg4 shape).
3. **Is a measured workload compute-, memory-, or dispatch-bound?**
   :func:`classify` joins ``cost_analysis`` flops/bytes (the compile
   plane records them per cache entry) with the PR-7 span tracer's
   measured dispatch/device self-time: dispatch share past the
   threshold -> ``dispatch_bound``; otherwise arithmetic intensity
   (flops/byte) vs the device's machine balance (peak flops / peak
   HBM bandwidth) decides ``compute_bound`` vs ``memory_bound``.
   Peaks come from a small advisory per-chip table
   (:data:`ROOFLINE_PEAKS`); on XLA:CPU everything here is advisory
   (PROFILE.md) -- the TPU session is the real record.

Everything in this module is host-side arithmetic over abstract
shapes: it launches nothing, allocates nothing device-side, and cannot
perturb a decision.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

import numpy as np

_SUBSYS_STATE = ("client_state", "rings")


def leaf_bytes(leaf) -> int:
    """Logical bytes of one array-like leaf (ShapeDtypeStruct,
    jax.Array, np.ndarray); 0 for None/scalars without dtype.  TPU
    lane tiling can pad small trailing dims -- the planner's
    ``slack_frac`` covers that margin."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def tree_bytes(tree) -> int:
    import jax

    return sum(leaf_bytes(x) for x in jax.tree_util.tree_leaves(tree))


def abstract_state(n: int, ring: int):
    """``EngineState`` shapes/dtypes for (n, ring) without allocating
    a byte (``jax.eval_shape`` over the real ``init_state``)."""
    import jax

    from ..engine.state import init_state

    return jax.eval_shape(functools.partial(init_state, n, ring))


def _abstract_tele(n: int, *, telemetry: bool, slo: bool,
                   flight_records: int) -> dict:
    """Abstract telemetry accumulators for the ledger walk and the
    epoch-output eval_shape -- shaped by the real constructors."""
    import jax

    out = {}
    if telemetry:
        from . import histograms as obshist
        out["hists"] = jax.eval_shape(obshist.hist_zero)
        out["ledger"] = jax.eval_shape(
            functools.partial(obshist.ledger_zero, n))
    if flight_records:
        from . import flight as obsflight
        out["flight"] = jax.eval_shape(
            functools.partial(obsflight.flight_init, flight_records))
    if slo:
        from . import slo as obsslo
        out["slo"] = jax.eval_shape(
            functools.partial(obsslo.window_zero, n))
    return out


def hbm_ledger(n: int, *, ring: int = 64, engine: Optional[str] = None,
               m: int = 0, k: int = 0, chain_depth: int = 4,
               select_impl: str = "sort", tag_width: int = 64,
               window_m: Optional[int] = None,
               calendar_impl: str = "minstop", ladder_levels: int = 8,
               telemetry: bool = False, slo: bool = False,
               flight_records: int = 0, lifecycle: bool = False,
               stream_chunk: int = 0) -> Dict[str, int]:
    """Per-subsystem resident HBM bytes for one configuration.

    Subsystems: ``client_state`` (the [N] SoA minus rings), ``rings``
    (the [N, Q] int64 tail pair -- the dominant term at bench shapes),
    ``telemetry_hists`` / ``telemetry_ledger`` / ``flight`` /
    ``slo_window`` (each only when enabled), ``lifecycle`` (the
    checkpoint-resident slot map), and -- when ``engine``/``m`` are
    given -- ``epoch_outputs``: the epoch program's decision/metric
    output blocks from ``jax.eval_shape`` of the real scan (state and
    accumulator echoes excluded: donated, they alias their inputs).
    ``stream_chunk`` > 1 multiplies the output blocks (the fused chunk
    stacks per-epoch outputs in HBM as scan outputs)."""
    import jax

    st = abstract_state(n, ring)
    rings = leaf_bytes(st.q_arrival) + leaf_bytes(st.q_cost)
    out: Dict[str, int] = {
        "client_state": tree_bytes(st) - rings,
        "rings": rings,
    }
    tele = _abstract_tele(n, telemetry=telemetry, slo=slo,
                          flight_records=flight_records)
    if "hists" in tele:
        out["telemetry_hists"] = tree_bytes(tele["hists"])
        out["telemetry_ledger"] = tree_bytes(tele["ledger"])
    if "flight" in tele:
        out["flight"] = tree_bytes(tele["flight"])
    if "slo" in tele:
        out["slo_window"] = tree_bytes(tele["slo"])
    if lifecycle:
        # the checkpoint-resident slot map (client-id <-> slot); the
        # boundary op vectors are transient launch arguments
        out["lifecycle"] = n * np.dtype(np.int64).itemsize
    if engine and m > 0:
        from ..engine import fastpath

        kw = fastpath.epoch_scan_kwargs(
            engine, k=k, chain_depth=chain_depth,
            select_impl=select_impl, tag_width=tag_width,
            window_m=window_m, calendar_impl=calendar_impl,
            ladder_levels=ladder_levels, with_metrics=True)
        now = jax.ShapeDtypeStruct((), np.dtype(np.int64))
        fn = functools.partial(fastpath.epoch_scan_fn(engine),
                               m=m, **kw, **tele)
        try:
            ep = jax.eval_shape(fn, st, now)
            skip = {"state", "hists", "ledger", "flight", "slo"}
            blocks = sum(
                tree_bytes(getattr(ep, f)) for f in ep._fields
                if f not in skip)
        except Exception:
            # an engine/backend combination eval_shape cannot trace
            # must not kill the planner: fall back to the dominant
            # closed-form term (the [m, k] decision block)
            blocks = m * max(k, 1) * 16
        out["epoch_outputs"] = blocks * max(stream_chunk, 1)
    return out


def projected_total(ledger: Dict[str, int]) -> int:
    return int(sum(ledger.values()))


class CapacityModel:
    """The exact per-subsystem linear model bytes(N) = a*N + b, fitted
    from two abstract ledgers (every subsystem is linear in N by
    construction -- the fit is exact, and it cannot rot because the
    ledgers walk the real pytrees)."""

    def __init__(self, slopes: Dict[str, float],
                 intercepts: Dict[str, float]):
        self.slopes = slopes
        self.intercepts = intercepts

    @property
    def bytes_per_client(self) -> float:
        return float(sum(self.slopes.values()))

    @property
    def fixed_bytes(self) -> float:
        return float(sum(self.intercepts.values()))

    def ledger(self, n: int) -> Dict[str, int]:
        return {s: int(round(self.slopes[s] * n + self.intercepts[s]))
                for s in self.slopes}

    def total(self, n: int) -> int:
        return projected_total(self.ledger(n))


_MODEL_N0, _MODEL_N1 = 256, 512
_MODEL_CACHE: Dict[tuple, CapacityModel] = {}


def capacity_model(**cfg) -> CapacityModel:
    """Fit the linear model for one knob setting (cached per cfg --
    the two eval_shape walks trace the epoch program)."""
    key = tuple(sorted(cfg.items()))
    model = _MODEL_CACHE.get(key)
    if model is None:
        l0 = hbm_ledger(_MODEL_N0, **cfg)
        l1 = hbm_ledger(_MODEL_N1, **cfg)
        dn = _MODEL_N1 - _MODEL_N0
        slopes = {s: (l1[s] - l0[s]) / dn for s in l0}
        inter = {s: l0[s] - slopes[s] * _MODEL_N0 for s in l0}
        model = _MODEL_CACHE[key] = CapacityModel(slopes, inter)
    return model


def projected_hbm(n: int, **cfg) -> int:
    """Projected resident HBM bytes for ``n`` clients at this knob
    setting -- the bench JSON line's ``projected_hbm_bytes``."""
    return capacity_model(**cfg).total(n)


def plan_capacity(budget_bytes: Optional[int] = None, *,
                  slack_frac: float = 0.1, device=None,
                  **cfg) -> dict:
    """Invert the ledger: max clients per chip for an HBM budget and a
    knob setting -- the mesh item's per-shard sizing question in one
    call.  ``budget_bytes`` defaults to the attached device's budget
    (:func:`device_hbm_budget`; raises ``ValueError`` when neither is
    known).  ``slack_frac`` reserves headroom for XLA temps, lane
    padding, and the runtime's own allocations."""
    if budget_bytes is None:
        budget_bytes = device_hbm_budget(device)
        if budget_bytes is None:
            raise ValueError(
                "no HBM budget: pass budget_bytes, set "
                "DMCLOCK_HBM_BUDGET_BYTES, or run where the device "
                "reports memory_stats()")
    model = capacity_model(**cfg)
    usable = int(budget_bytes * (1.0 - slack_frac))
    per = model.bytes_per_client
    n = int(max((usable - model.fixed_bytes) // max(per, 1e-9), 0))
    while n > 0 and model.total(n) > usable:
        n -= 1
    return {
        "max_clients": n,
        "budget_bytes": int(budget_bytes),
        "usable_bytes": usable,
        "slack_frac": slack_frac,
        "bytes_per_client": per,
        "fixed_bytes": model.fixed_bytes,
        "projected_bytes": model.total(n),
        "ledger": model.ledger(n),
        "config": dict(cfg),
    }


def fits(n: int, budget_bytes: int, *, slack_frac: float = 0.1,
         **cfg) -> bool:
    """Does an ``n``-client configuration fit the budget (with the
    planner's slack)?  The round-trip property the ci gate pins:
    ``fits(plan_capacity(b)["max_clients"], b)`` is True and any
    larger N refuses."""
    return projected_hbm(n, **cfg) <= int(budget_bytes
                                          * (1.0 - slack_frac))


def device_hbm_budget(device=None) -> Optional[int]:
    """Detected per-device memory budget in bytes.
    ``DMCLOCK_HBM_BUDGET_BYTES`` overrides (testable, and the escape
    hatch for runtimes that hide ``memory_stats``); CPU boxes report
    None -- host RAM is not the resource this plane manages."""
    env = os.environ.get("DMCLOCK_HBM_BUDGET_BYTES")
    if env:
        try:
            # 0 means "detection disabled" (the DMCLOCK_COMPILE_PLANE
            # =0 convention), not a zero-byte budget that would gate
            # every workload
            return int(env) or None
        except ValueError:
            pass
    import jax

    try:
        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
        if stats:
            v = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit")
            if v:
                return int(v)
    except Exception:
        pass
    return None


# ----------------------------------------------------------------------
# roofline attribution
# ----------------------------------------------------------------------

# Per-chip peaks keyed by ``device_kind`` as JAX reports it: (dense
# peak flops/s, HBM bytes/s).  They gate a CLASSIFICATION (which side
# of the machine-balance ridge a workload sits on), not a utilization
# claim; the scheduler's integer ops count as cost_analysis "flops".
# TPU rows: Google Cloud TPU documentation, per-chip bf16 peak and HBM
# bandwidth ("TPU v5e": 197 TFLOP/s, 819 GB/s; "TPU v4", "TPU v5p").
# The cpu row is a rough XLA:CPU host ballpark for CPU-side tests.  A
# device kind not listed here is an error, never a default.
ROOFLINE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),     # v5e
    "TPU v5": (459e12, 2765e9),         # v5p
    "TPU v4": (275e12, 1228e9),
    "cpu": (2e11, 5e10),
}


def device_peaks(device=None) -> dict:
    """(peak flops/s, peak HBM bytes/s, label) for ``device`` (default:
    the first local device), from :data:`ROOFLINE_PEAKS` by its exact
    ``device_kind``; an unknown kind raises ``KeyError``."""
    import jax

    d = device if device is not None else jax.local_devices()[0]
    kind = d.device_kind
    if kind not in ROOFLINE_PEAKS:
        raise KeyError(f"no roofline peaks for device_kind {kind!r} "
                       f"(known: {sorted(ROOFLINE_PEAKS)})")
    pf, pb = ROOFLINE_PEAKS[kind]
    return {"label": kind, "peak_flops": pf, "peak_bytes_per_s": pb}


def classify(*, flops: float, bytes_accessed: float,
             device_time_s: Optional[float] = None,
             dispatch_time_s: Optional[float] = None,
             peak_flops: Optional[float] = None,
             peak_bytes_per_s: Optional[float] = None,
             dispatch_share_warn: float = 0.5) -> dict:
    """The classification rule (docs/OBSERVABILITY.md "Capacity
    plane"):

    1. with measured times, dispatch self-time share of
       (dispatch + device) past ``dispatch_share_warn`` ->
       ``dispatch_bound`` (the host dispatch tax dominates; no amount
       of kernel tuning helps before the streaming loop does);
    2. otherwise arithmetic intensity (flops / bytes accessed) vs the
       machine balance (peak flops / peak bandwidth): below the ridge
       -> ``memory_bound``, at/above -> ``compute_bound``;
    3. no flops/bytes at all -> ``unknown``.
    """
    if peak_flops is None or peak_bytes_per_s is None:
        pk = device_peaks()
        peak_flops = peak_flops or pk["peak_flops"]
        peak_bytes_per_s = peak_bytes_per_s or pk["peak_bytes_per_s"]
    out: dict = {"peak_flops": peak_flops,
                 "peak_bytes_per_s": peak_bytes_per_s,
                 "machine_balance": peak_flops / peak_bytes_per_s}
    if device_time_s is not None and dispatch_time_s is not None \
            and (device_time_s + dispatch_time_s) > 0:
        share = dispatch_time_s / (device_time_s + dispatch_time_s)
        out["dispatch_share"] = share
        if share > dispatch_share_warn:
            out["bound_class"] = "dispatch_bound"
            return out
    if not flops and not bytes_accessed:
        out["bound_class"] = "unknown"
        return out
    ai = flops / max(bytes_accessed, 1.0)
    out["arithmetic_intensity"] = ai
    if device_time_s:
        out["achieved_flops_per_s"] = flops / device_time_s
        out["achieved_bytes_per_s"] = bytes_accessed / device_time_s
    out["bound_class"] = "compute_bound" \
        if ai >= out["machine_balance"] else "memory_bound"
    return out


def classify_bench_row(row: dict, *, peaks: Optional[dict] = None,
                       dispatch_share_warn: float = 0.5) -> dict:
    """Roofline verdict for one bench workload row: joins the row's
    ``cost_analysis`` (per-launch flops/bytes) with its ``spans``
    block's measured per-launch dispatch/device self-time when spans
    ran; without spans the verdict is intensity-only (rule 2)."""
    ca = row.get("cost_analysis") or {}
    sp = row.get("spans") or {}
    kw: dict = dict(flops=float(ca.get("flops", 0.0)),
                    bytes_accessed=float(ca.get("bytes_accessed",
                                                0.0)),
                    dispatch_share_warn=dispatch_share_warn)
    if "device_ms_per_launch" in sp and "dispatch_ms_per_launch" in sp:
        kw["device_time_s"] = sp["device_ms_per_launch"] / 1e3
        kw["dispatch_time_s"] = sp["dispatch_ms_per_launch"] / 1e3
    if peaks:
        kw["peak_flops"] = peaks.get("peak_flops")
        kw["peak_bytes_per_s"] = peaks.get("peak_bytes_per_s")
    return classify(**kw)


def publish_capacity_metrics(registry, *, projected_bytes=None,
                             budget_bytes=None, max_clients=None,
                             workload: Optional[str] = None) -> None:
    """``dmclock_capacity_*`` gauges on the scrape endpoint."""
    lbl = {"workload": workload} if workload else None
    if projected_bytes is not None:
        registry.gauge(
            "dmclock_capacity_projected_hbm_bytes",
            "projected resident HBM bytes for the workload's knob "
            "setting (obs.capacity ledger; docs/OBSERVABILITY.md "
            "capacity plane)", labels=lbl).set(float(projected_bytes))
    if budget_bytes is not None:
        registry.gauge(
            "dmclock_capacity_budget_bytes",
            "detected device HBM budget (memory_stats bytes_limit or "
            "DMCLOCK_HBM_BUDGET_BYTES)").set(float(budget_bytes))
    if max_clients is not None:
        registry.gauge(
            "dmclock_capacity_max_clients",
            "plan_capacity() max clients per chip at the current "
            "budget and knob setting", labels=lbl) \
            .set(float(max_clients))
