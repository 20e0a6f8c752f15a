"""readbacks_per_query.count: `VectorStats.readbacks` (host syncs) summed
over the window's requests, per completed request."""


def read(run):
    n = run.counters.get("readbacks")
    return n / run.completed if n is not None and run.completed else None
