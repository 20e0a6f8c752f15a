"""dispatch_buffers_per_superstep.count: `VectorStats.dispatch_buffers`
(the flattened input plus output leaves of every superstep call: the
device buffers the runtime takes in and hands back) over
`VectorStats.supersteps`, both summed over the window's requests
(scheduler dispatch, core/scheduler.py)."""


def read(run):
    n = run.counters.get("dispatch_buffers")
    steps = run.counters.get("supersteps")
    return n / steps if n is not None and steps else None
