"""readback_ms_per_query.count: host time blocked in the `cemr.readback`
spans (`VectorStats.span_readback_s`: the `jax.device_get` of superstep
results), summed over the window's requests, in ms per completed request
(core/scheduler.py)."""


def read(run):
    s = run.counters.get("span_readback_s")
    return 1e3 * s / run.completed if s is not None and run.completed \
        else None
