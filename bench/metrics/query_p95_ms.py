"""query_p95_ms: closed loop, 95th percentile of the wall time of every
`Matcher.count` in the window."""
import numpy as np


def read(run):
    return float(np.percentile(run.latencies_ms, 95)) if run.latencies_ms \
        else None
