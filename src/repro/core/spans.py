"""Host spans at the matcher's layer boundaries.

`span(name, stats, field, **args)` opens a `jax.profiler.TraceAnnotation`,
so the span lands in a profiler trace on the same clock as the device's
ops, nested under whatever span the host thread is in, and adds its wall
time in seconds to `stats.<field>`. A span costs the same whether a
profiler is running or not. Spans whose stats object does not exist yet
when they open leave `stats` out and hand their time to the caller as
`.seconds`.

The spans (docs/engine.md lists the fields that hold their totals):
`cemr.count` (one `Matcher.count`), `cemr.plan` (its compile and plan
build), `cemr.host_dfs` (the ref engine's enumeration), `cemr.enumerate`
(the vector engine's run), `cemr.dispatch` (one superstep dispatch),
`cemr.readback` (one superstep readback) and `cemr.process` (one readback's
host work; trace only).
"""
from __future__ import annotations

import time

import jax

__all__ = ["span"]


class span:
    """Context manager: one profiler span, its duration added to
    `stats.<field>` (when given) and kept as `.seconds`."""

    __slots__ = ("_ann", "_stats", "_field", "_t0", "seconds")

    def __init__(self, name: str, stats=None, field: str | None = None,
                 **args):
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._stats, self._field = stats, field
        self.seconds = 0.0

    def set_args(self, **args) -> None:
        """Add arguments known only after the span opened."""
        self._ann.set_metadata(**args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.seconds = (time.perf_counter_ns() - self._t0) * 1e-9
        self._ann.__exit__(*exc)
        if self._stats is not None:
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + self.seconds)
        return False
