"""select_ms_per_epoch: device time of the dmc.select phase, selection
(classify, chain, pack, sort, prefix test), per traced epoch
(phase_ms.py)."""

from perfbench.harness import discover


def read(run):
    return discover._module(run.cell.root, "metrics",
                            "phase_ms").ms_per_epoch(run, "select")
