"""device_idle.count: share of the traced window in which no operation ran
on the device (1 - union of op intervals / window), closed-loop count."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
