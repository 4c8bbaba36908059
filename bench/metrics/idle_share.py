"""Device: the share of the window in which no operation ran on the
device, in percent (mean over the devices traced)."""

from bench import trace as tr


def read(run):
    summ = tr.summary(run.trace)
    if not summ["window_s"] or not run.trace.device_ops:
        return None
    return 100.0 * (1.0 - summ["busy_s"] / summ["window_s"])
