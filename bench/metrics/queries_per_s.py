"""queries_per_s: closed loop, completed count requests over the whole
window (its first request's start to its last answer)."""


def read(run):
    return run.completed / run.window_s if run.completed else None
