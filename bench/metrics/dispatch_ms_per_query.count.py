"""dispatch_ms_per_query.count: host time in the `cemr.dispatch` spans
(`VectorStats.span_dispatch_s`: superstep lookup, the jitted call with its
argument handling and cursor upload, the ring-buffer fold), summed over
the window's requests, in ms per completed request (core/scheduler.py)."""


def read(run):
    s = run.counters.get("span_dispatch_s")
    return 1e3 * s / run.completed if s is not None and run.completed \
        else None
