"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and turns it, with the run's seed, into the requests of one run.

`"loop": "closed"` - one client asks its next query when the last one has
returned. The queries come in rounds: each round holds every pool query
once, shuffled by the seed. So every seed asks the same work in another
order.
"""
from __future__ import annotations

import numpy as np

__all__ = ["closed_requests"]


def closed_requests(mix: dict, pool_size: int, seed: int):
    """Endless pool indices for a closed loop."""
    rng = np.random.default_rng([int(seed), 1])
    while True:
        yield from (int(k) for k in rng.permutation(pool_size))
