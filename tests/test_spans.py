"""Host spans of `Matcher.count` and the superstep loop (core/spans.py).

A count runs inside `jax.profiler.trace`; the trace's host plane must hold
the `cemr.*` spans nested as the layers call each other, one span per
superstep dispatch and per readback, and the span totals on the stats
must add up. The padded-copy counter is checked against its formula.
"""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.api import Dataset, Matcher, MatchOptions
from repro.core.graph import random_walk_query, synthetic_labeled_graph
from repro.core.scheduler import TileScheduler
from repro.kernels.bitmap_intersect import LANES


@pytest.fixture(scope="module")
def pair():
    data = synthetic_labeled_graph(60, 5.0, 3, seed=3, power_law=False)
    return data, random_walk_query(data, 5, seed=13)


def traced_count(tmp_path, data, query, **opts):
    """(outcome, [(name, start_ns, end_ns, args)]) of one warm count."""
    m = Matcher(Dataset.from_graph(data),
                MatchOptions(limit=10**9, tile_rows=16, **opts))
    m.count(query)                                   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        out = m.count(query)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("cemr.")]
    return out, spans


def named(spans, name):
    return [s for s in spans if s[0] == name]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("engine,run_span", [("vector", "cemr.enumerate"),
                                             ("ref", "cemr.host_dfs")])
def test_count_span_holds_plan_and_one_run_span(tmp_path, pair, engine,
                                                run_span):
    out, spans = traced_count(tmp_path, *pair, engine=engine)
    whole, = named(spans, "cemr.count")
    assert whole[3]["engine"] == engine and whole[3]["query"]
    plan, = named(spans, "cemr.plan")
    assert inside(plan, whole)
    runs = [s for s in spans if s[0] in ("cemr.enumerate", "cemr.host_dfs")]
    assert [s[0] for s in runs] == [run_span] and inside(runs[0], whole)
    assert out.compile_s == out.stats.span_plan_s
    assert out.elapsed_s == getattr(out.stats, run_span.replace(
        "cemr.", "span_") + "_s")
    assert out.stats.span_count_s >= out.elapsed_s + out.compile_s


def test_one_dispatch_span_per_superstep_and_readback_span_per_sync(
        tmp_path, pair):
    out, spans = traced_count(tmp_path, *pair, engine="vector")
    st = out.stats
    run, = named(spans, "cemr.enumerate")
    dispatch = named(spans, "cemr.dispatch")
    readback = named(spans, "cemr.readback")
    assert st.supersteps > 1
    assert len(dispatch) == st.supersteps
    assert len(readback) == st.readbacks
    assert len(named(spans, "cemr.process")) >= st.readbacks
    assert all(inside(s, run) for s in dispatch + readback)
    assert all("boundary" in s[3] for s in dispatch)
    assert st.span_dispatch_s + st.span_readback_s <= st.span_enumerate_s
    assert st.span_enumerate_s <= st.span_count_s


def test_span_totals_are_not_compared(pair):
    """Two fresh sessions counting one query give equal stats: the
    timings differ, every counter agrees."""
    a, b = (Matcher(Dataset.from_graph(pair[0]),
                    MatchOptions(engine="vector", limit=10**9, tile_rows=16))
            .count(pair[1]).stats for _ in range(2))
    assert a.span_enumerate_s > 0 and b.span_enumerate_s > 0
    assert a == b


def test_pad_copy_bytes_follow_the_formula(tmp_path, pair):
    """Every dispatch charges, for each extend its ladder runs through the
    Pallas kernel, S * W_pad * 4 bytes per backward-pair table whose width
    W is not a multiple of 128 words (here every table: W is a few
    words). The dispatch spans name their boundary."""
    out, spans = traced_count(tmp_path, *pair, engine="vector",
                              intersect="pallas")
    eng = Matcher(Dataset.from_graph(pair[0])).compile(pair[1]) \
        .vector_engine(MatchOptions(tile_rows=16, intersect="pallas"))
    sched = TileScheduler(eng)

    def expected(b):
        total = 0
        for _, bms, exit_si in sched._ladder(b):
            chain = bms + ([] if exit_si == sched._n_stages else [exit_si])
            for sj in chain:
                stage = eng._stages[sj]
                if stage[0] != "extend":
                    continue
                for (_, u) in stage[1].bk_pairs:
                    s, w = eng.plan.tables[(u, stage[1].vertex)].shape
                    assert w % LANES
                    total += s * (-(-w // LANES) * LANES) * 4
        return total

    boundaries = [s[3]["boundary"] for s in named(spans, "cemr.dispatch")]
    want = sum(expected(b) for b in boundaries)
    assert want > 0
    assert out.stats.pad_copy_bytes == want


def test_no_pad_copies_without_the_kernel(pair):
    out = Matcher(Dataset.from_graph(pair[0])).count(
        pair[1], engine="vector", intersect="jnp", tile_rows=16)
    assert out.stats.supersteps > 0 and out.stats.pad_copy_bytes == 0
