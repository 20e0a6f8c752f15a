"""Reduce a JAX profiler trace to the benchmark's device numbers.

A trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`) has one plane
per device (`/device:TPU:<i>`), whose line of XLA ops holds every operation
that ran on it, and host planes whose lines hold the benchmark's own spans
(`jax.profiler.TraceAnnotation`, named `bench.*`). All events share one
clock, in nanoseconds.

* The window is the host span `bench.window`.
* Busy time of a device is the union of its op intervals inside the window;
  `busy_s` is its mean over the devices used, and the idle share is
  1 - busy_s / window_s.
* The idle gaps of the first device are labelled by the `bench.*` span that
  overlaps them most: what the host was doing while the device waited.
* Kernel events are found by name (`events_named`).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Reduction", "reduce", "reduce_dir", "union_ns", "gaps_ns"]

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    device: int
    stats: dict


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    ops: list                 # every device op inside the window
    idle_gaps: list           # (label, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def events_named(self, pattern: str) -> list:
        """Device ops whose name matches the regular expression."""
        rx = re.compile(pattern)
        return [o for o in self.ops if rx.search(o.name)]

    def top_ops(self) -> list:
        """(op name, seconds summed over devices), largest first."""
        tot: dict[str, float] = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0.0) + o.dur_ns * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        return {"device_ops": [list(t) for t in self.top_ops()[:TOP]],
                "idle_gaps": [list(t) for t in self.idle_gaps[:TOP]]}


def union_ns(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps_ns(busy: list, lo: float, hi: float) -> list:
    """The [start, end] stretches of [lo, hi] that `busy` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, min(s, hi)])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return [g for g in out if g[1] > g[0]]


def _device_id(plane_name: str) -> int | None:
    m = re.fullmatch(r"/device:[A-Z]+:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def _label(gap, spans) -> str:
    best, label = 0.0, "(no bench span)"
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, label = ov, name
    return label


def reduce(pd, *, n_devices: int) -> Reduction:
    """Reduce a `ProfileData` (see the module doc)."""
    spans, planes = [], {}
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            planes[dev] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0]
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    used = sorted(planes)[:n_devices]
    if not used:
        raise ValueError("no device plane in the trace")
    ops, busy_total, first_busy = [], 0.0, []
    for dev in used:
        ivals = []
        lines = {line.name: line for line in planes[dev].lines}
        if OPS_LINE not in lines:
            raise ValueError(f"device {dev} has no {OPS_LINE!r} line, only "
                             f"{sorted(lines)}")
        for ev in lines[OPS_LINE].events:
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            ops.append(Op(ev.name, s, e - s, dev,
                          {k: v for k, v in ev.stats}))
            ivals.append((s, e))
        busy = union_ns(ivals)
        busy_total += sum(e - s for s, e in busy)
        if dev == used[0]:
            first_busy = busy
    gaps = sorted(((_label(g, spans), (g[1] - g[0]) * 1e-9)
                   for g in gaps_ns(first_busy, lo, hi)),
                  key=lambda t: -t[1])
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total / len(used) * 1e-9,
                     n_devices=len(used), ops=ops, idle_gaps=gaps)


def reduce_dir(trace_dir: str, *, n_devices: int) -> Reduction:
    """Reduce the newest trace written under `trace_dir`."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    return reduce(pd, n_devices=n_devices)
