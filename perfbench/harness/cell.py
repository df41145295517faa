"""One run of one cell: set-up, the measured window, the reference
check, the metrics, and the result line."""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from . import discover, generator, loops, program


class NoChip(RuntimeError):
    pass


def devices_for(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except (RuntimeError, NotImplementedError):
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Run:
    """What a per-layer reader may read: the cell, the loop's spans and
    counts, compile seconds, the reduced trace, kernel counts, peaks."""

    def __init__(self, cell, loop, spans, compile_s, trace, device_kind):
        self.cell = cell
        self.loop = loop
        self.spans = spans
        self.compile_s = compile_s
        self.trace = trace
        self.device_kind = device_kind

    @property
    def config(self):
        return self.cell.config

    def peaks(self):
        return discover.peaks(self.cell.root, self.device_kind)

    def kernel(self, name):
        return discover.kernel(self.cell.root, name)

    def traced_epochs(self) -> int:
        return self.loop.traced_epochs

    def host_s(self) -> float:
        """Host time of the window's loop: launch and drain spans less
        the time the host spent blocked on the device (a paced loop's
        sleep until an epoch is due is not counted)."""
        sec = self.spans.seconds
        return sec.get("harness.launch", 0.0) + \
            sec.get("harness.drain", 0.0) - \
            sec.get("stream.device_wait", 0.0)

    def host_epochs(self) -> int:
        return self.loop.window_epochs

    def roofline(self, kernel_name: str):
        """Least time of the traced calls from their bytes at the HBM
        peak over their traced time, in %; None where none ran.  The
        kernels here do integer VPU work and no MXU work, so their
        bound is the bytes."""
        if self.trace is None:
            return None
        k = self.kernel(kernel_name)
        calls, secs = self.trace.matching(k.trace_pattern(self.config))
        if calls == 0 or secs <= 0:
            return None
        least = calls * k.bytes_per_call(self.config) / \
            self.peaks()["hbm_bytes_per_s"]
        return 100.0 * least / secs


def _check(cell, con, pre, arrivals, loop, allow: bool) -> dict:
    """Every epoch of the run against the plain reference."""
    import jax
    import jax.numpy as jnp

    from . import reference as R

    cfg, tr = cell.config, cell.traffic
    ref = R.initial(con, pre, int(cfg["ring"]))
    cost = jnp.asarray(con["cost"], jnp.int64)
    kw = dict(waves=int(cfg["engine"]["waves"]),
              epoch_ns=int(tr["epoch_ns"]), rho=int(cfg["rho"]),
              delta=int(cfg["delta"]), allow=allow)
    eng = cfg["engine"]
    cap = int(eng["m"]) * int(eng["k"])
    capacity = float(cfg["capacity_ops_per_s_per_client"]) * \
        int(cfg["clients"]) * kw["epoch_ns"] / 1e9
    verdicts, streams, per_epoch = [], [], []
    for e, (rec, raw) in enumerate(zip(loop.records, loop.raw)):
        ordered = rec.slots is not None
        ref, v, keys = R.epoch(ref, jnp.asarray(raw),
                               jnp.asarray(rec.served), e * kw["epoch_ns"],
                               cost, keep_keys=ordered, **kw)
        verdicts.append(v)
        if ordered:
            t = rec.slots.size
            pad = max(cap, t) - t
            streams.append(R.stream_check(
                keys, ref.order,
                jnp.asarray(np.pad(rec.slots, (0, pad))),
                jnp.asarray(np.pad(rec.phase, (0, pad))),
                jnp.asarray(np.pad(rec.lb, (0, pad))), t))
        per_epoch.append(rec.decisions)
    v = np.asarray(jax.device_get(jnp.stack(verdicts)))
    s = np.asarray(jax.device_get(jnp.stack(streams))) if streams else \
        np.zeros((len(verdicts), 3), np.int64)
    idle = (v[:, 3] < capacity) & (v[:, 4] > 0)
    bad_epoch = (v[:, 1] > 0) | (v[:, 2] > 0) | idle | (s.sum(axis=1) > 0)
    first = loop.first_window_epoch
    win = slice(first, first + loop.window_epochs)
    dec = np.asarray(per_epoch, dtype=np.int64)
    raw_tot = np.asarray([int(r.sum()) for r in loop.raw], np.int64)
    # the server's capacity binds from above too: an epoch may pull past
    # capacity x epoch by the configuration's stated batch margin only
    over = dec - int(capacity)
    queued0 = int(pre["depth"]) * int(cfg["clients"])
    out = {
        "epochs_checked": int(len(v)),
        "over_capacity": int(max(over.max(initial=0), 0)),
        "over_capacity_margin": int(cfg["over_capacity_margin"]),
        "queued_at_close": int(queued0 + v[:, 0].sum() - dec.sum()),
        "paced": tr["mode"] == "paced",
        "epochs_not_serial": int(((v[:, 1] > 0) | (v[:, 2] > 0)).sum()),
        "ineligible_serves": int(v[:, 1].sum()),
        "idle_with_work": int(idle.sum()),
        "stream_out_of_order": int(s[:, 0].sum()),
        "phase_mismatches": int(s[:, 1].sum()),
        "limit_break_mismatches": int(s[:, 2].sum()),
        "window_decisions": int(dec[win].sum()),
        "window_arrivals": int(raw_tot[win].sum()),
        "window_admitted": int(v[win, 0].sum()),
        "failed_decisions": int(dec[win][bad_epoch[win]].sum()),
        "ordered": bool(streams),
    }
    return out


def _limits(chk: dict) -> list:
    """(name, value, limit, ok): each number compared, with its limit.
    Every comparison with the reference is exact (limit 0); the pulls
    past capacity are held to the configuration's stated margin."""
    rows = [("epochs_not_serial", chk["epochs_not_serial"], 0),
            ("ineligible_serves", chk["ineligible_serves"], 0),
            ("idle_with_work", chk["idle_with_work"], 0),
            ("over_capacity", chk["over_capacity"],
             chk["over_capacity_margin"])]
    if chk["paced"]:
        # an arrival the admission clamp refuses is never decided, so
        # the latency cannot see it: below the knee there are none
        rows.append(("refused_arrivals", chk["window_arrivals"] -
                     chk["window_admitted"], 0))
    if chk["ordered"]:
        rows += [("stream_out_of_order", chk["stream_out_of_order"], 0),
                 ("phase_mismatches", chk["phase_mismatches"], 0),
                 ("limit_break_mismatches",
                  chk["limit_break_mismatches"], 0)]
    out = [(n, v, lim, v <= lim) for n, v, lim in rows]
    out.append(("window_decisions", chk["window_decisions"], ">=1",
                chk["window_decisions"] >= 1))
    return out


def _percentile(xs: np.ndarray, p: float) -> float:
    return float(np.percentile(xs, p, method="linear"))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str, t_proc0: float, require_chip: bool = True,
             out=None, err=None) -> int:
    """Run cell ``name`` once; print the result line.  Returns the exit
    code.  With ``require_chip`` False (tests only) it runs on
    whatever JAX has."""
    out = out or sys.stdout
    err = err or sys.stderr
    bench = discover.load_benchmark(root)
    cell = discover.Cell(bench, name, root)
    devs = devices_for(cell.chips, require_chip)
    if cell.chips != 1:
        raise NotImplementedError("this harness drives one-chip cells")

    import jax

    from .spans import CompileClock, Spans

    jax.config.update("jax_enable_x64", True)
    clock = CompileClock()
    spans = Spans()
    cfg, tr = cell.config, cell.traffic
    con = generator.contracts(cfg, seed)
    pre = generator.preload(cfg, tr, con, seed)
    arrivals = generator.Arrivals(cfg, tr, con, seed)
    kw = program.chunk_kwargs(cfg, tr, con)
    t_init = time.perf_counter()
    with jax.default_device(devs[0]):
        state = program.build_state(cfg, con, pre)
        jax.block_until_ready(state)
        t_state = time.perf_counter()
        loop, state = loops.run(
            tr["mode"], state, kw, arrivals, spans, seconds=seconds,
            warmup_launches=int(tr["warmup_launches"]), trace=trace)
    setup_s = loop.setup_end - t_proc0
    compile_s = clock.snapshot()[0]
    peak = memory_peak(devs)
    del state
    for rec in loop.records:
        rec.finish(int(cfg["clients"]))
    kind = devs[0].device_kind
    trace_obj = None
    if trace:
        from .trace import Trace, find_xplane

        trace_obj = Trace(find_xplane(loop.trace_dir))
        loops.cleanup(loop)

    print(f"setup_phases start_to_jax={t_init - t_proc0} "
          f"state={t_state - t_init} warmup={loop.setup_end - t_state} "
          f"compile_s={compile_s} cache_hits={clock.hits} "
          f"cache_misses={clock.misses}", file=out, flush=True)
    if len(loop.launch_return) > 1:
        gaps = np.diff([loop.window_t0] + loop.launch_return)
        print(f"window epochs={loop.window_epochs} decisions_per_epoch="
              f"{loop.window_decisions / max(loop.window_epochs, 1)} "
              f"launch_s p50={float(np.median(gaps))} max="
              f"{float(gaps.max())} at={int(gaps.argmax())} "
              f"of={gaps.size}", file=out, flush=True)
        if tr["mode"] == "closed" and len(loop.launch_return) > 3:
            # does the rate drift over the window?  decisions per second
            # of the first and the second half of its launches
            h = len(loop.launch_return) // 2
            per = loop.launch_decisions
            t = [loop.window_t0] + loop.launch_return
            print(f"rate_first_half={sum(per[:h]) / (t[h] - t[0])} "
                  f"rate_second_half={sum(per[h:]) / (t[-1] - t[h])}",
                  file=out, flush=True)
    if loop.handover_lag_s:
        lag = np.asarray(loop.handover_lag_s) * 1e3
        print(f"generator_lag_ms p50={_percentile(lag, 50)} "
              f"p95={_percentile(lag, 95)} max={float(lag.max())} "
              f"epochs={lag.size}", file=out, flush=True)

    # ---- end-to-end metrics
    values = {"setup_s": setup_s}
    if tr["mode"] == "closed":
        values["decisions_per_s"] = loop.window_decisions / loop.window_s
    lat = None
    if tr["mode"] == "paced":
        lat = loops.latencies_ms(loop, int(cfg["ring"]),
                                 int(cfg["engine"]["waves"]),
                                 int(tr["epoch_ns"]),
                                 int(cfg["clients"]))
        if lat.size:
            values["decision_latency_p50_ms"] = _percentile(lat, 50)
            values["decision_latency_p95_ms"] = _percentile(lat, 95)
        half = lat.size // 2
        if half:
            print(f"requests_decided={lat.size} p50_first_half="
                  f"{_percentile(lat[:half], 50)} p50_second_half="
                  f"{_percentile(lat[half:], 50)} p95="
                  f"{_percentile(lat, 95)} p95_first_half="
                  f"{_percentile(lat[:half], 95)} p95_second_half="
                  f"{_percentile(lat[half:], 95)}", file=out, flush=True)

    # ---- correctness, after the window and with the program's state
    # freed (a reference run first would set the memory peak)
    t_ref = time.perf_counter()
    with jax.default_device(devs[0]):
        chk = _check(cell, con, pre, arrivals, loop,
                     bool(con["allow_limit_break"]))
    print(f"reference_s={time.perf_counter() - t_ref} "
          f"epochs={chk['epochs_checked']} "
          f"window_epochs={loop.window_epochs} "
          f"stream_fallbacks={loop.fallbacks} "
          f"queued_at_close={chk['queued_at_close']} "
          f"window_arrivals={chk['window_arrivals']} "
          f"window_admitted={chk['window_admitted']}", file=out, flush=True)
    limits = _limits(chk)
    correct = all(ok for *_, ok in limits)

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"cell {name} lists {m['name']} but the "
                               "run did not measure it")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        r = Run(cell, loop, spans, compile_s, trace_obj, kind)
        for m in cell.per_layer:
            v = discover.metric_reader(root, m["name"]).read(r)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    if tr["mode"] == "paced":
        attempted = chk["window_arrivals"]
        failed = chk["window_arrivals"] - chk["window_admitted"] + \
            chk["failed_decisions"]
    else:
        attempted = chk["window_decisions"]
        failed = chk["failed_decisions"]
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace_obj is not None:
        device["busy_s"] = trace_obj.busy_s()
        device["window_s"] = trace_obj.window_s
        line["breakdown"] = {"device_ops": trace_obj.top_ops(10),
                             "idle_gaps": trace_obj.idle_gaps(10)}
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, v, lim, _ in limits}
    for n, v, lim, ok in limits:
        print(f"check {n} {v} limit {lim} {'ok' if ok else 'FAILED'}",
              file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return 0
