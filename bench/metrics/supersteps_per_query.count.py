"""supersteps_per_query.count: `VectorStats.supersteps` summed over the
window's requests, per completed request (scheduler, core/scheduler.py)."""


def read(run):
    n = run.counters.get("supersteps")
    return n / run.completed if n is not None and run.completed else None
