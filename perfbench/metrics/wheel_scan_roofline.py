"""wheel_scan_roofline: the Pallas wheel_scan kernel's least time from
its HBM bytes (kernels/wheel_scan.py) at the peak, over its traced
time, in %.  The kernel runs at two bucket counts (the class wheel
and the stop wheel); each call counts at its own bytes."""

from perfbench.harness import discover


def read(run):
    t = run.trace
    if t is None:
        return None
    k = discover.kernel(run.cell.root, "wheel_scan")
    least = secs = 0.0
    for nb in k.BUCKETS:
        calls, s = t.matching(k.trace_pattern(run.config, nb))
        least += calls * k.bytes_per_call(run.config, nb)
        secs += s
    if secs <= 0:
        return None
    return 100.0 * least / run.peaks()["hbm_bytes_per_s"] / secs
