"""budget_ms_per_epoch: device time of the dmc.budget phase, the
calendar engine's pull-budget cut (the level's request count and the
selection of the first requests of the serial order up to the budget
left), per traced epoch (phase_ms.py).  None where the program has no
such phase."""

from perfbench.harness import discover


def read(run):
    try:
        from dmclock_tpu.obs.compile_plane import PHASES
    except ImportError:
        return None
    if "budget" not in PHASES:
        return None
    return discover._module(run.cell.root, "metrics",
                            "phase_ms").ms_per_epoch(run, "budget")
