"""The control and the planted faults that `correct` has to catch.

Each entry of `FAULTS` is a context manager that breaks the timed path
underneath the harness, so that a run through it must come out with
`correct` false:

* `control`: the plain reference put in the program's place, computing
  with one guarantee broken: the limit is not cut exactly (it keeps the
  count of the block that crossed it, see `reference.py`);
* `answer_altered`: every count one higher where the program produces it;
* `dropped_operand`: the intersect kernel ANDs one table row fewer than
  the plan asks (the last backward neighbour), so candidates that miss an
  edge survive;
* `half_rows`: the intersect kernel's result is kept for the first half of
  each frontier tile only; the other half's partial embeddings are dropped.

The two kernel faults replace the intersect function every engine is built
with (`core/engine.py: _resolve_intersect_fn`): the Pallas kernel on a TPU,
its jnp oracle elsewhere, broken as said. `bench/control.py` runs a cell
under one of them on the chip; `tests/bench/test_bench_correct.py` does so
at a small size on the CPU.
"""
from __future__ import annotations

import contextlib

from bench import reference

__all__ = ["FAULTS"]


@contextlib.contextmanager
def _patched(obj, name, fn):
    old = getattr(obj, name)
    setattr(obj, name, fn)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _reference_outcome(matcher, query, limit):
    from repro.api import MatchOutcome
    from repro.core.engine import VectorStats
    g = matcher.dataset.graph
    edges = [(u, int(w)) for u in range(query.n)
             for w in query.neighbors(u) if u < w]
    c = reference.count_embeddings(g, query.labels, edges, limit,
                                   exact_cut=False)
    return MatchOutcome(count=c, engine="ref", elapsed_s=0.0,
                        timed_out=False, stats=VectorStats())


@contextlib.contextmanager
def control():
    from repro.api import Matcher

    def count(self, query, options=None, **kw):
        opts = self._resolve_options(options, kw)
        return _reference_outcome(self, query, opts.limit)

    with _patched(Matcher, "count", count):
        yield


@contextlib.contextmanager
def answer_altered():
    from repro.api import Matcher
    count0 = Matcher.count

    def count(self, *a, **kw):
        out = count0(self, *a, **kw)
        out.count += 1
        return out

    with _patched(Matcher, "count", count):
        yield


def _broken_intersect(breaks):
    """A context that builds every engine with the intersect function
    `breaks(fn)`, `fn` being the kernel (on a TPU) or its oracle."""
    import jax.numpy as jnp
    from repro.core import engine
    from repro.kernels import ops

    def resolve(intersect):
        fn = ops.make_intersect_fn(use_pallas=ops.on_tpu())

        def kernel(tables, idxs):     # the oracle's popcount is int64 on x64
            r, pop = fn(tables, idxs)
            return r, pop.astype(jnp.int32)
        return breaks(kernel)

    return _patched(engine, "_resolve_intersect_fn", resolve)


def dropped_operand():
    def breaks(fn):
        def broken(tables, idxs):
            if len(tables) < 2:
                return fn(tables, idxs)
            return fn(tables[:-1], idxs[:, :-1])
        return broken
    return _broken_intersect(breaks)


def half_rows():
    def breaks(fn):
        def broken(tables, idxs):
            r, pop = fn(tables, idxs)
            half = idxs.shape[0] // 2
            return r.at[half:].set(0), pop.at[half:].set(0)
        return broken
    return _broken_intersect(breaks)


FAULTS = {"control": control, "answer_altered": answer_altered,
          "dropped_operand": dropped_operand, "half_rows": half_rows}
