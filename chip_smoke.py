#!/usr/bin/env python
"""Bring-up smoke of the CEMR tile engine on one TPU chip.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Everything runs in this one process, through the entry points a user calls
(`Matcher.count`, `match_many`, `stream`, `MatchService`), with default
`MatchOptions` except where a phase names an option:

  (a) device  - JAX must find a TPU; there is no CPU fallback.
  (b) exact   - both intersect kernels against `kernels/ref.py` at the
                full-scale widths, then the fig7 workload (scale 0.03) and
                full-scale `human` queries on `engine="vector"` with
                `intersect="auto"` and `"fused"`: every count is under the
                limit and equals `engine="ref"`.
  (c) full    - dblp and wordnet at their Table-2 sizes, random-walk
                queries of 8 and 12 vertices on each at limit 10^5:
                `count` runs on the vector engine under `engine="auto"` and
                equals the ref count; `match_many` agrees; the first 1,000
                embeddings of `stream` are valid (labels, edges, injective,
                distinct).
  (d) service - an inline `MatchService` answers 16 wordnet requests for
                the first query of (c), with no failure, no degradation
                and the count of (c).

`--chips 4` runs the first phase (c) query of each graph under
`MatchOptions(mesh=4)` against `mesh=None` and checks that the mesh spans
four distinct chips.

The query counts and sizes are cut to what fits the 1,200 s a cold run
may take: every query compiles its own superstep programs, 20-80 s of a
cold run on a v5e, and the service compiles superbatch programs of its
own. A cold count of a 24-vertex dblp query did not finish in 960 s on a
v5e (PERF.md, Findings), so the queries stop at 12 vertices. The graphs
stay at full size.

Each phase prints one line: wall time, `compile_s` (the host-side plan
compile `MatchOutcome.compile_s` reports, summed), the XLA compiles and
their seconds, and the device's `peak_bytes_in_use` so far. The last line
of a passing run is one JSON object naming the device; a failing run exits
non-zero without it. The compile cache goes where
`JAX_COMPILATION_CACHE_DIR` says, else to `.jax_cache/` in this checkout.
"""
from __future__ import annotations

import argparse
import itertools
import json
import logging
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

from benchmarks.common import fig7_workloads, make_queries  # noqa: E402
from repro.api import Dataset, Matcher, MatchOptions  # noqa: E402
from repro.core.graph import random_walk_query  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

FULL_SCALE = ("dblp", "wordnet")
QUERY_SIZES = (8, 12)
SHARDED_QUERIES = 1           # per graph, under --chips 4
LIMIT = 100_000               # the paper's embedding limit
STREAM_CHECKED = 1_000
SERVICE_REQUESTS = 16
SERVICE_QUERIES = 1           # the service's queries: the first of phase (c)
KERNEL_WIDTHS = (1, 124, 152, 660)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# XLA compiles seen by this process: (count, seconds), fed by a listener
_XLA = [0, 0.0]


def _on_event(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _XLA[0] += 1
        _XLA[1] += seconds


class _CaughtFailures(logging.Handler):
    """Collects what the runtime logs where it catches an execution failure
    and carries on (a batched chunk retried item by item, an item whose
    executor died): the run's answers may still be right, but the path
    under test failed."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records: list[logging.LogRecord] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(record)


_CAUGHT = _CaughtFailures()


class Phase:
    """Times one phase and prints its line when the phase ends: wall time,
    host plan compile (`MatchOutcome.compile_s`), XLA compiles and their
    seconds, and the device's peak bytes so far. A failure the runtime
    caught and logged during the phase fails it."""

    def __init__(self, name: str):
        self.name, self.compile_s, self.notes = name, 0.0, []

    def outcome(self, out):
        check(out is not None, f"{self.name}: no outcome")
        check(not out.timed_out, f"{self.name}: outcome timed out")
        self.compile_s += out.compile_s
        return out

    def __enter__(self):
        self.t0, self.xla0 = time.perf_counter(), list(_XLA)
        self.caught0 = len(_CAUGHT.records)
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        caught = _CAUGHT.records[self.caught0:]
        check(not caught, f"{self.name}: the runtime caught "
                          f"{len(caught)} failure(s), first: "
                          f"{caught[0].getMessage() if caught else ''}")
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        print(f"phase={self.name} wall_s={time.perf_counter() - self.t0:.3f} "
              f"compile_s={self.compile_s:.3f} "
              f"xla_compiles={_XLA[0] - self.xla0[0]} "
              f"xla_compile_s={_XLA[1] - self.xla0[1]:.3f} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}"
              + "".join(f" {n}" for n in self.notes), flush=True)
        return False


# ------------------------------------------------------------------ phase (a)
def phase_device() -> dict:
    import jax

    from repro.runtime.workers import host_chips
    with Phase("a_device") as ph:
        devs = jax.devices()
        d = devs[0]
        check(d.platform == "tpu",
              f"needs a TPU, but JAX's first device is on platform "
              f"{d.platform!r}")
        platform, pci_chips, holds = host_chips()
        ph.notes += [f"kind={d.device_kind!r}", f"count={len(devs)}",
                     f"jax={jax.__version__}", f"pci_tpu_chips={pci_chips}",
                     f"pool_platform={platform}", f"holds_tpu={holds}"]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------ phase (b)
def check_kernels(rng) -> int:
    """Both kernels, compiled, against the jnp oracles at the widths of the
    full-scale tables, with out-of-range indices among the in-range ones."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.bitmap_intersect import (bitmap_intersect_pallas,
                                                fused_expand_intersect_pallas)
    n = 0
    for w, k in itertools.product(KERNEL_WIDTHS, (1, 2, 3)):
        tabs = tuple(jnp.asarray(rng.integers(0, 2**32, (300 + 7 * j, w),
                                              dtype=np.uint32))
                     for j in range(k))
        idxs = jnp.asarray(rng.integers(-320, 320, (253, k)), jnp.int32)
        got = bitmap_intersect_pallas(tabs, idxs, interpret=False)
        want = ref.bitmap_intersect_ref(tabs, idxs)
        idx = jnp.asarray(rng.integers(0, 300, (64, 5)), jnp.int32)
        rows = jnp.asarray(rng.integers(0, 64, 253), jnp.int32)
        bitpos = jnp.asarray(rng.integers(0, 300, 253), jnp.int32)
        slots = (5, 0, 3)[:k]
        got_f = fused_expand_intersect_pallas(tabs, idx, rows, bitpos,
                                              slots=slots, interpret=False)
        want_f = ref.fused_expand_intersect_ref(tabs, idx, rows, bitpos,
                                                slots=slots)
        for g, e in zip(got + got_f, want + want_f):
            check(np.array_equal(np.asarray(g), np.asarray(e)),
                  f"kernel differs from kernels/ref.py at W={w} k={k}")
        n += 2
    return n


def phase_exact(human_scale: float = 1.0) -> None:
    from repro.kernels import ops
    with Phase("b_exact") as ph:
        check(ops.default_interpret() is False,
              "Pallas would run in interpret mode")
        ph.notes.append(f"kernel_cases={check_kernels(np.random.default_rng(0))}")
        work = [(name, data, [q for _, q in sized])
                for name, (data, sized) in fig7_workloads(0.03).items()]
        human = Dataset.synthetic("human", scale=human_scale).graph
        work.append(("human", human,
                     [q for _, q in make_queries(human, sizes=(4, 6),
                                                 per_size=3)]))
        limit = MatchOptions().limit
        n = 0
        for name, data, queries in work:
            t0 = time.perf_counter()
            m = Matcher(Dataset.from_graph(data))
            for q in queries:
                want = ph.outcome(m.count(q, engine="ref")).count
                check(want < limit, f"{name}: ref count {want} reached "
                                    f"the limit; not an exact differential")
                for mode in ("auto", "fused"):
                    out = ph.outcome(m.count(q, engine="vector",
                                             intersect=mode))
                    check(out.count == want,
                          f"{name} intersect={mode}: vector {out.count} "
                          f"!= ref {want}")
                n += 1
            print(f"  {name} queries={len(queries)} "
                  f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
        ph.notes.append(f"queries={n}")


# ------------------------------------------------------------------ phase (c)
def full_scale_queries(ds: Dataset) -> list:
    queries = []
    for i, size in enumerate(QUERY_SIZES):
        for attempt in range(100):
            try:
                queries.append(random_walk_query(
                    ds.graph, size, seed=1000 * i + 7 * attempt + size))
                break
            except RuntimeError:
                continue
        else:
            raise SmokeFailure(f"no {size}-vertex random walk on {ds!r}")
    return queries


def check_embedding(emb: dict, q, g) -> None:
    verts = [emb[u] for u in range(q.n)]
    check(len(set(verts)) == q.n, "embedding is not injective")
    check(all(g.labels[v] == q.labels[u] for u, v in enumerate(verts)),
          "embedding breaks a vertex label")
    for u in range(q.n):
        for w in q.neighbors(u):
            check(g.has_edge(verts[u], verts[int(w)]),
                  "embedding misses a data edge")


def phase_full(scale: float = 1.0) -> dict:
    expected = {}
    with Phase("c_full") as ph:
        for name in FULL_SCALE:
            ds = Dataset.synthetic(name, scale=scale)
            queries = full_scale_queries(ds)
            m = Matcher(ds, MatchOptions(limit=LIMIT))
            counts = []
            for i, q in enumerate(queries):
                xla0 = list(_XLA)
                out = ph.outcome(m.count(q))
                check(out.engine_used == "vector",
                      f"{name} q{i}: engine='auto' ran {out.engine_used}")
                want = ph.outcome(m.count(q, engine="ref")).count
                check(out.count == want,
                      f"{name} q{i}: vector {out.count} != ref {want}")
                cq = m.compile(q)
                tables = sum(t.nbytes for t in cq.plan.tables.values())
                print(f"  {name} q{i} |V(q)|={q.n} "
                      f"candidate_rows={int(cq.cs.sizes().sum())} "
                      f"table_bytes={tables} count={out.count} "
                      f"enum_s={out.elapsed_s:.3f} "
                      f"supersteps={out.stats.supersteps} "
                      f"xla_compiles={_XLA[0] - xla0[0]} "
                      f"xla_compile_s={_XLA[1] - xla0[1]:.3f}", flush=True)
                counts.append(out.count)
            # the same Matcher: its plans and programs are warm, so these
            # check the batch and streaming entry points, not the compiler
            t0 = time.perf_counter()
            many = [ph.outcome(o).count for o in m.match_many(queries)]
            check(many == counts, f"{name}: match_many {many} != {counts}")
            t1 = time.perf_counter()
            for i, q in enumerate(queries):
                embs = list(itertools.islice(
                    m.stream(q, limit=STREAM_CHECKED), STREAM_CHECKED))
                check(len(embs) == min(STREAM_CHECKED, counts[i]),
                      f"{name} q{i}: stream gave {len(embs)} embeddings")
                check(len({tuple(sorted(e.items())) for e in embs})
                      == len(embs), f"{name} q{i}: repeated embeddings")
                for e in embs:
                    check_embedding(e, q, ds.graph)
            print(f"  {name} match_many_s={t1 - t0:.3f} "
                  f"stream_s={time.perf_counter() - t1:.3f}", flush=True)
            expected[name] = (ds, queries, counts)
            ph.notes.append(f"{name}_counts={counts}")
    return expected


# ------------------------------------------------------------------ phase (d)
def phase_service(ds: Dataset, queries: list, counts: list) -> None:
    from repro.runtime.service import MatchService, ServiceConfig
    with Phase("d_service") as ph:
        svc = MatchService(ds, options=MatchOptions(limit=LIMIT))
        # every bucket holds the same mix of the queries, so the buckets
        # after the first reuse its superbatch programs
        bucket = ServiceConfig().bucket_size
        n = min(SERVICE_QUERIES, len(queries))
        reqs = [(r % bucket) * n // bucket for r in range(SERVICE_REQUESTS)]
        tickets = [svc.submit(queries[i], priority="batch", limit=LIMIT,
                              max_steps=None, deadline_s=3600.0)
                   for i in reqs]
        svc.drain()
        st = svc.stats
        shed = st["shed_admission"] + st["shed_expired"]
        check(st["failed"] == 0, f"service failed {st['failed']} requests")
        check(st["degraded"] == 0, f"service degraded {st['degraded']}")
        check(len(tickets) == st["completed"] + shed,
              f"offered {len(tickets)} != completed {st['completed']} "
              f"+ shed {shed}")
        for t, i in zip(tickets, reqs):
            r = svc.result(t.request_id)
            check(r is not None and r.ok and r.count == counts[i],
                  f"request {t.request_id}: {r} != count {counts[i]}")
        ph.notes += [f"offered={len(tickets)}", f"completed={st['completed']}",
                     f"shed={shed}", f"failed={st['failed']}",
                     f"degraded={st['degraded']}"]


# --------------------------------------------------------------- --chips 4
def phase_sharded(n_chips: int, scale: float = 1.0) -> None:
    import jax

    from repro.launch.mesh import make_enum_mesh
    with Phase(f"sharded_{n_chips}") as ph:
        mesh = make_enum_mesh(n_chips)
        ids = set() if mesh is None else {d.id for d in mesh.devices.flat}
        check(len(ids) == n_chips and len(jax.devices()) >= n_chips,
              f"mesh={n_chips} spans {len(ids)} of {len(jax.devices())} "
              f"devices")
        lanes = supersteps = 0
        for name in FULL_SCALE:
            ds = Dataset.synthetic(name, scale=scale)
            m = Matcher(ds, MatchOptions(limit=LIMIT, engine="vector"))
            for i, q in enumerate(full_scale_queries(ds)[:SHARDED_QUERIES]):
                seq = ph.outcome(m.count(q))
                shd = ph.outcome(m.count(q, mesh=n_chips))
                check(shd.count == seq.count,
                      f"{name} q{i}: mesh={n_chips} {shd.count} != "
                      f"mesh=None {seq.count}")
                check(shd.stats.shard_lanes > 0,
                      f"{name} q{i}: no sharded superstep ran")
                lanes += shd.stats.shard_lanes
                supersteps += shd.stats.supersteps
                print(f"  {name} q{i} count={seq.count} "
                      f"seq_s={seq.elapsed_s:.3f} sharded_s={shd.elapsed_s:.3f} "
                      f"shard_lanes={shd.stats.shard_lanes}", flush=True)
        # shard_lanes sums the live lanes of every sharded superstep: more
        # lanes than supersteps means supersteps did run on several chips
        check(lanes > supersteps,
              f"{lanes} live lanes over {supersteps} supersteps: the work "
              f"never spread across the mesh")
        ph.notes += [f"mesh_device_ids={sorted(ids)}", f"shard_lanes={lanes}",
                     f"sharded_supersteps={supersteps}"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded path across four chips")
    args = ap.parse_args()
    enable_compile_cache()
    logging.getLogger("repro").addHandler(_CAUGHT)
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    try:
        device = phase_device()
        if args.chips == 1:
            phase_exact()
            expected = phase_full()
            phase_service(*expected["wordnet"])
        else:
            phase_sharded(args.chips)
    except Exception as e:                           # noqa: BLE001
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
