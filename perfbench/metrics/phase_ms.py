"""The shared reader of the <phase>_ms_per_epoch metrics: device time
of one phase of the stream chunk per traced epoch.  An op counts
toward a phase when it is a leaf op in the traced window and the head
of its HLO text (its instruction name) maps to that phase in the
program's ``dmclock_tpu.obs.compile_plane.op_phases("stream.chunk")``;
the time is averaged over devices.  None where there is no trace or
the program gives no map (a program without device phases)."""


def ms_per_epoch(run, phase: str):
    t = run.trace
    if t is None or not t.ops or run.traced_epochs() == 0:
        return None
    try:
        from dmclock_tpu.obs.compile_plane import op_phases
    except ImportError:
        return None
    phases = op_phases("stream.chunk")
    if not phases:
        return None
    ns = 0.0
    for ops in t.ops.values():
        for s, e, hlo, leaf in ops:
            if leaf and s >= t.lo and e <= t.hi and \
                    phases.get(hlo.split(" ", 1)[0]) == phase:
                ns += e - s
    return ns / 1e6 / t.devices / run.traced_epochs()
