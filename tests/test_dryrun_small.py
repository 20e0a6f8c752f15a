"""Dry-run machinery on a small faked mesh (subprocess: device count must be
set before jax init). Exercises the same lower+compile+roofline path the
512-chip run uses, at 8 devices with the CEMR engine cell + roofline parser
unit checks."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.roofline import collective_bytes, roofline_terms

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.dryrun import dryrun_engine_cell
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    res = dryrun_engine_cell(mesh, frontier_rows=1024, space=4096, k_bwd=2,
                             verbose=False)
    print("RESULT:" + json.dumps({"ok": res["ok"],
                                  "dominant": res["roofline"]["dominant"],
                                  "chips": res["chips"]}))
""")


@pytest.mark.slow
def test_engine_cell_compiles_on_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    out = json.loads(line[0][len("RESULT:"):])
    assert out["ok"] and out["chips"] == 8
    assert out["dominant"] in ("memory", "compute", "collective")


def test_collective_bytes_parser():
    hlo = """
      %ag = f32[64,128]{1,0} all-gather(%x), dimensions={0}
      %ar = bf16[32]{0} all-reduce(%y), to_apply=%sum
      %rs = f32[16,16]{1,0} reduce-scatter(%z), dimensions={0}
      %aa = u32[8,8]{1,0} all-to-all(%w), dimensions={1}
      %cp = s32[4]{0} collective-permute(%v), source_target_pairs={{0,1}}
      %dot = f32[64,64]{1,0} dot(%a, %b)
    """
    got = collective_bytes(hlo)
    assert got["all-gather"] == 64 * 128 * 4
    assert got["all-reduce"] == 32 * 2
    assert got["reduce-scatter"] == 16 * 16 * 4
    assert got["all-to-all"] == 8 * 8 * 4
    assert got["collective-permute"] == 4 * 4
    assert "dot" not in got


def test_roofline_terms_math():
    t = roofline_terms({"flops": 1.97e14, "bytes accessed": 8.19e11}, "",
                       chips=4, device_kind="TPU v5 lite",
                       model_flops=1.97e14 * 2)
    assert abs(t.compute_s - 1.0) < 1e-9       # 1.97e14 per dev / peak
    assert abs(t.memory_s - 1.0) < 1e-9
    assert t.dominant in ("compute", "memory")
    assert abs(t.useful_fraction - 0.5) < 1e-9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_roofline_terms_refuse_unknown_device_kind(kind):
    """A kind without a sourced entry in PEAKS raises; it never borrows
    the v5e numbers."""
    with pytest.raises(KeyError, match="no peak rates"):
        roofline_terms({"flops": 1.0}, "", chips=1, device_kind=kind)
