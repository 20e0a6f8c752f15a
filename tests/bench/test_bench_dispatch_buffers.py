"""The reader of the program's superstep buffer counter, on synthetic
runs: the ratio where the run has both counters, nothing where it lacks
one (a program without the counter) or ran no superstep."""
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = "dispatch_buffers_per_superstep.count"


def run(**counters):
    return harness.Run(seconds=51.0, completed=100, counters=counters)


@pytest.mark.parametrize("has,value", [
    (run(dispatch_buffers=4_200, supersteps=100), 42.0),
    (run(dispatch_buffers=121, supersteps=1, span_dispatch_s=3.3), 121.0),
])
def test_reads_buffers_per_superstep(has, value):
    assert harness.read_metric(ROOT, NAME, has) == pytest.approx(value)


@pytest.mark.parametrize("lacks", [
    run(supersteps=100),                       # a program without it
    run(dispatch_buffers=0, supersteps=0),     # no superstep ran
    run(dispatch_buffers=0),
    run(),
], ids=["no-counter", "no-supersteps", "no-superstep-counter", "empty"])
def test_reads_nothing_without_supersteps(lacks):
    assert harness.read_metric(ROOT, NAME, lacks) is None
