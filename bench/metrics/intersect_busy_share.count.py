"""intersect_busy_share.count: device time of the intersect kernel's ops
(named `cemr_gather_and` by `kernels/bitmap_intersect.py`; the op's own
name, not the ops that read its output) over the device's busy time, in
the traced window, in %."""

KERNEL = r"^%?cemr_gather_and(\.\d+)?(\s|$)"


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    ops = run.trace.events_named(KERNEL)
    if not ops:
        return None
    busy_ns = run.trace.busy_s * 1e9 * run.trace.n_devices
    return 100.0 * sum(o.dur_ns for o in ops) / busy_ns
