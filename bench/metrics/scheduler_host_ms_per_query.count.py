"""scheduler_host_ms_per_query.count: host time in the vector engine's
`cemr.enumerate` spans outside its dispatch and readback spans
(`VectorStats.span_enumerate_s - span_dispatch_s - span_readback_s`: the
scheduler's own loop, readback parsing, merges, leaf fallback), summed
over the window's requests, in ms per completed request."""


def read(run):
    c = run.counters
    keys = ("span_enumerate_s", "span_dispatch_s", "span_readback_s")
    if not run.completed or any(k not in c for k in keys):
        return None
    return 1e3 * (c[keys[0]] - c[keys[1]] - c[keys[2]]) / run.completed
