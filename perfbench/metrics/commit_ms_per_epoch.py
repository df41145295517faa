"""commit_ms_per_epoch: device time of the dmc.commit phase, the commit
(boundary, chain commit, promote pass), per traced epoch
(phase_ms.py)."""

from perfbench.harness import discover


def read(run):
    return discover._module(run.cell.root, "metrics",
                            "phase_ms").ms_per_epoch(run, "commit")
