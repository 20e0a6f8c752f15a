"""The readers of the program's host spans, its padded-copy counter and
the intersect kernel's device time, on synthetic runs: each reads its
value, and reads nothing where the run lacks its input (a program
without the spans, or an untraced run)."""
from pathlib import Path

import pytest

from bench import harness, trace_reduce

ROOT = Path(__file__).resolve().parents[2]

COUNTERS = {"span_count_s": 30.0, "span_plan_s": 0.5,
            "span_enumerate_s": 25.0, "span_dispatch_s": 9.0,
            "span_readback_s": 4.0, "span_host_dfs_s": 4.4,
            "pad_copy_bytes": 6_000_000, "supersteps": 3500}
KERNEL_OP = ("%cemr_gather_and.3 = (u32[256,128]{1,0:T(8,128)}, "
             "s32[256,1]{1,0}) custom-call(s32[512] %a, u32[4861,1,128] %b),"
             " custom_call_target=\"tpu_custom_call\"")
READER_OP = ("%get-tuple-element.5 = u32[256,128]{1,0} get-tuple-element("
             "(u32[256,128], s32[256,1]) %cemr_gather_and.3), index=0")


def trace(names_durs):
    ops = [trace_reduce.Op(n, 0.0, d, 0, {}) for n, d in names_durs]
    return trace_reduce.Reduction(window_s=4.0, busy_s=0.1, n_devices=1,
                                  ops=ops, idle_gaps=[])


def run(counters=COUNTERS, tr=None, completed=100):
    return harness.Run(seconds=51.0, completed=completed,
                       counters=dict(counters), trace=tr)


WITH_KERNEL = trace([(KERNEL_OP, 20e6), ("cemr_gather_and.4", 5e6),
                     (READER_OP, 3e6), ("fusion.7", 70e6)])


def without(*keys):
    return {k: v for k, v in COUNTERS.items() if k not in keys}


# metric, a run that has its input, the value, runs that lack it
CASES = [
    ("dispatch_ms_per_query.count", run(), 90.0,
     [run(without("span_dispatch_s")), run(completed=0)]),
    ("readback_ms_per_query.count", run(), 40.0,
     [run(without("span_readback_s")), run(completed=0)]),
    ("scheduler_host_ms_per_query.count", run(), 120.0,
     [run(without("span_enumerate_s")), run(without("span_dispatch_s")),
      run(without("span_readback_s")), run(completed=0)]),
    ("host_dfs_ms_per_query.count", run(), 44.0,
     [run(without("span_host_dfs_s")), run(completed=0)]),
    ("pad_copy_mb_per_query.count", run(), 0.06,
     [run(without("pad_copy_bytes")), run(completed=0)]),
    # 20 ms + 5 ms of kernel over 100 ms busy; the op reading its output
    # and the fusion are not the kernel
    ("intersect_busy_share.count", run(tr=WITH_KERNEL), 25.0,
     [run(), run(tr=trace([(READER_OP, 3e6), ("fusion.7", 70e6)])),
      run(tr=trace([("bitmap_intersect_pallas.1", 9e6)]))]),
]


@pytest.mark.parametrize("name,has,value,lacks", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_reads_its_input_and_nothing_without_it(name, has, value,
                                                       lacks):
    assert harness.read_metric(ROOT, name, has) == pytest.approx(value)
    for r in lacks:
        assert harness.read_metric(ROOT, name, r) is None
