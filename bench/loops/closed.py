"""Request loop `closed`: one client asks `Matcher.count` for its next pool
query when the last one has returned, back to back for `seconds`.

The queries come in rounds: each round holds every pool query once,
shuffled by the seed. So every seed asks the same work in another order.
Set-up counts every pool query twice, so that the window finds each of
its programs compiled and loaded.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import harness

__all__ = ["run", "requests"]


def requests(mix: dict, pool_size: int, seed: int):
    """Endless pool indices for a closed loop."""
    rng = np.random.default_rng([int(seed), 1])
    while True:
        yield from (int(k) for k in rng.permutation(pool_size))


def run(run, config, mix, seed, ds, pool, tw, events, t_start):
    """One client: `Matcher.count` back to back for `run.seconds` (every
    loop's signature, `bench/harness.py` says what each argument is)."""
    from repro.api import Matcher
    m = Matcher(ds, harness.match_options(config))
    with harness.span("bench.warmup"):
        for k, q in enumerate(pool):
            t = time.perf_counter()
            m.count(q)
            m.count(q)
            print(f"bench: warm-up {config['pool'][k].get('set', k)} "
                  f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
    answers = []
    draws = requests(mix, len(pool), seed)
    ev0 = events.snapshot()
    run.setup_s = time.perf_counter() - t_start
    tw.start()
    t0 = time.perf_counter()
    end = t0
    while end - t0 < run.seconds:
        k = next(draws)
        with harness.span("bench.count"):
            t = time.perf_counter()
            out = m.count(pool[k])
            end = time.perf_counter()
        run.latencies_ms.append((end - t) * 1e3)
        answers.append((k, out.count, out.timed_out))
        run.engines[out.engine] = run.engines.get(out.engine, 0) + 1
        harness.add_stats(run.counters, out.stats)
        tw.tick(end - t0)
    run.window_s = end - t0
    run.attempted = run.completed = len(answers)
    run.failed = sum(1 for a in answers if a[2])
    return answers, ev0, events.snapshot()
