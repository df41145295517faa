"""window_ms_per_epoch: device time of the dmc.window phase, the ring
window (shift tiling, the ring_rotate kernel, the per-batch row
selects), per traced epoch (phase_ms.py)."""

from perfbench.harness import discover


def read(run):
    return discover._module(run.cell.root, "metrics",
                            "phase_ms").ms_per_epoch(run, "window")
