"""pad_copy_mb_per_query.count: `VectorStats.pad_copy_bytes` (the
zero-padded table copies the Pallas intersect kernel makes, charged per
superstep dispatch) summed over the window's requests, in MB (1e6 bytes)
per completed request (kernels/bitmap_intersect.py)."""


def read(run):
    n = run.counters.get("pad_copy_bytes")
    return n / run.completed / 1e6 if n is not None and run.completed \
        else None
