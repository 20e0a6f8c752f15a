"""BENCHMARK.json keeps to the benchmark's contract, and the harness finds
every file a cell is made of by name."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
TEXT = re.compile(r"[^\t\n\r]{1,200}")
METRICS = MAN["end_to_end"] + MAN["per_layer"]
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reported(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench", "tests/bench"]
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert isinstance(MAN["run_seconds"], int) and \
        1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("name", [m["name"] for m in METRICS]
                         + [c["name"] for c in MAN["configs"]]
                         + [w["name"] for w in MAN["workloads"]]
                         + [w["traffic"] for w in MAN["workloads"]]
                         + [k for c in MAN["configs"] for k in c["reduced"]])
def test_names_use_allowed_characters(name):
    assert NAME.fullmatch(name), name


def test_names_are_unique():
    for group in (METRICS, MAN["configs"], MAN["workloads"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.fullmatch(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    if metric["name"] in E2E:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert TEXT.fullmatch(metric["layer"])
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_each_moves_is_reported_by_every_cell_that_reports_the_metric(metric):
    moves = E2E[metric["moves"]]
    cells = [w["name"] for w in MAN["workloads"] if reported(metric,
                                                             w["name"])]
    assert cells
    for cell in cells:
        assert reported(moves, cell), (metric["name"], cell)


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_each_cell_is_whole(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert TEXT.fullmatch(cell["why"])
    e2e = [m["name"] for m in MAN["end_to_end"]
           if reported(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reported(m, cell["name"]) for m in MAN["per_layer"])
    _, _, config, mix = harness.load_cell(ROOT, cell["name"])
    assert (ROOT / "bench" / "loops" / f"{mix['loop']}.py").is_file()
    assert (ROOT / "bench" / "graphs"
            / f"{config['graph']['model']}.py").is_file()
    assert config["pool"] and config["limit"] > 0


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_entries(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert TEXT.fullmatch(cfg["source"]) and TEXT.fullmatch(cfg["why"])
    assert cfg["file"].startswith("bench/") and len(cfg["reduced"]) <= 16
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    for k in cfg["reduced"]:
        assert k in data and k in data["study"]
    assert sum(w["config"] == cfg["name"] for w in MAN["workloads"]) >= 1


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    need = runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert need <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_a_cell_of_new_files_is_found(tiny_root):
    man, cell, config, mix = harness.load_cell(tiny_root, "tiny.count")
    assert cell["traffic"] == "tiny_mix" and mix["loop"] == "closed"
    assert config["name"] == "tiny"
    names = [m["name"] for m in harness.metric_names(man, "tiny.count",
                                                     per_layer=True)]
    assert "device_idle.count" in names
    man, cell, config, mix = harness.load_cell(tiny_root, "tiny-ring.replay")
    assert mix["loop"] == "replay" and config["graph"]["model"] == \
        "cliquering"
    assert [m["name"] for m in harness.metric_names(
        man, "tiny-ring.replay", per_layer=True)] == ["replayed.tiny"]
    with pytest.raises(harness.CellError):
        harness.load_cell(tiny_root, "no.such")


def test_a_cell_of_new_files_runs(tiny_root, run_cell):
    rc, out = run_cell(tiny_root, "tiny-ring.replay")
    assert rc == 0 and out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"replay_mean_ms", "setup_s"}
    pool = harness.load_cell(tiny_root, "tiny-ring.replay")[2]["pool"]
    assert out["attempted"] == len(pool) == 4
    assert list(out["checks"]) == ["wrong_counts", "not_asked_once"]
    assert out["checks"]["not_asked_once"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("part", ["model", "loop"])
def test_an_unknown_model_or_loop_gives_no_result(tiny_root, tmp_path,
                                                  run_cell, part):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    name, section = {"model": ("configs/tiny.json", "graph"),
                     "loop": ("traffic/tiny_mix.json", None)}[part]
    path = root / "bench" / name
    data = json.loads(path.read_text())
    (data[section] if section else data)[part] = "no_such"
    path.write_text(json.dumps(data))
    with pytest.raises(harness.CellError, match="no_such"):
        harness.load_cell(root, "tiny.count")
    assert run_cell(root, "tiny.count") == (2, None)


def test_no_tpu_means_no_result(tmp_path, capsys):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(MAN))
    rc = harness.main(["--workload", MAN["workloads"][0]["name"], "--seed", "1",
                       "--seconds", "1"], root=ROOT)
    assert rc != 0
    assert capsys.readouterr().out == ""
