"""`correct` holds on sound runs and fails under the control and under each
fault the cell can have, driven through the harness at a small size on the
CPU (bench/faults.py)."""
import pytest

from bench import faults


def test_sound_run_is_correct(tiny_root, run_cell):
    rc, out = run_cell(tiny_root, "tiny.count")
    assert rc == 0
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert out["metrics"]["setup_s"]["value"] > 0
    assert {"queries_per_s", "query_p95_ms"} <= set(out["metrics"])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_control_and_faults_are_caught(tiny_root, run_cell, fault):
    with faults.FAULTS[fault]():
        rc, out = run_cell(tiny_root, "tiny.count")
    assert rc == 0
    assert out["correct"] is False
    check = out["checks"]["wrong_counts"]
    assert check["value"] > check["limit"]
