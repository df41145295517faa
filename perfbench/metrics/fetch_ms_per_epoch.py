"""fetch_ms_per_epoch: host time of the program's stream.fetch span
(the guard readback, the decision stream's device_get and the epoch
views) per epoch served in the window."""


def read(run):
    secs = run.spans.seconds.get("stream.fetch")
    epochs = run.host_epochs()
    if secs is None or epochs == 0:
        return None
    return 1e3 * secs / epochs
