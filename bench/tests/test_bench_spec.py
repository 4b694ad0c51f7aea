"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and per-layer reader is found by name and agrees with the
benchmark's contract; adding a cell and a metric takes files alone."""
import json
import math
import re

import jax  # noqa: F401 — JAX first, so the harness's cache setting stays out
import pytest

import bench_tiny
import spec
from common import ROOT

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    # a full check of 24 cells fits in its 43,200 s budget
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_bounds():
    metrics = B["end_to_end"] + B["per_layer"]
    names = [x["name"] for x in B["configs"] + B["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert [m["bound"] for m in B["end_to_end"] if m["name"] == "setup_s"] \
        == [0.25]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_it_must(cell):
    e2e = [m["name"] for m in B["end_to_end"] if spec.applies(m, cell)]
    layer = [m for m in B["per_layer"] if spec.applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = spec.load_cell(cell)
    assert c["limits"] and all(v > 0 for v in c["limits"].values())
    assert (ROOT / "bench" / f"path_{c['path']}.py").is_file()
    assert c["chips"] in (1, 4)


@pytest.mark.parametrize("config", [c["name"] for c in B["configs"]])
def test_config_file_is_what_the_program_runs(config):
    entry = next(c for c in B["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["file"] == f"bench/configs/{config}.json"
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert all(k in cfg for k in cfg["reduced"])
    spec.arch_for(cfg)                   # raises where file and program differ
    assert any(w["config"] == config for w in B["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in B["per_layer"]])
def test_reader_declares_its_layer(metric):
    entry = next(m for m in B["per_layer"] if m["name"] == metric)
    r = spec.load_reader(metric)
    assert (r.LAYER, r.MOVES) == (entry["layer"], entry["moves"])


def test_missing_files_are_errors(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    (root / "bench/workloads" / f"{bench_tiny.TRAIN}.json").unlink()
    with pytest.raises(FileNotFoundError):
        spec.load_cell(bench_tiny.TRAIN, root)
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", root)
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric", root)
    other = bench_tiny.make_root(tmp_path / "other")
    (other / "bench/traffic/tsfl.json").write_text('{"path": "nowhere"}')
    with pytest.raises(ValueError):
        spec.load_cell(bench_tiny.TRAIN, other)


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path, monkeypatch,
                                                      capsys):
    """A new configuration, traffic mix, cell and per-layer reader, added
    as files and entries; the harness runs the cell and reports the
    metric without a change to any file it already had."""
    bench_tiny.patch(monkeypatch)
    root = bench_tiny.make_root(tmp_path)
    before = {p.name: p.read_bytes() for p in (root / "bench").glob("*.py")}
    b = root / "bench"
    cfg = json.loads((b / "configs/tiny.json").read_text())
    (b / "configs/tiny-b.json").write_text(json.dumps(dict(cfg, batch_size=1)))
    tr = json.loads((b / "traffic/tsfl.json").read_text())
    (b / "traffic/tsfl-long.json").write_text(json.dumps(dict(tr, seq_len=48)))
    (b / "workloads/train.tiny-b.tsfl-long.json").write_text(json.dumps(
        {"config": "tiny-b", "traffic": "tsfl-long", "split_layer": 1,
         "limits": bench_tiny.TRAIN_LIMITS}))
    (b / "metrics/rounds_traced.py").write_text(
        'LAYER, MOVES = "train entry", "train_tokens_per_s"\n\n\n'
        'def read(ctx):\n    return float(ctx["run"]["rounds"])\n')
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "tiny-b", "source": "x",
                          "file": "bench/configs/tiny-b.json",
                          "reduced": [], "why": "x"})
    bj["workloads"].append({"name": "train.tiny-b.tsfl-long",
                            "config": "tiny-b", "traffic": "tsfl-long",
                            "chips": 1, "why": "x"})
    for m in bj["end_to_end"] + bj["per_layer"]:
        if m.get("moves", m["name"]) == "train_tokens_per_s" and "workloads" in m:
            m["workloads"].append("train.tiny-b.tsfl-long")
    bj["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                            "better": "higher", "source": "program_counter",
                            "layer": "train entry",
                            "moves": "train_tokens_per_s",
                            "workloads": ["train.tiny-b.tsfl-long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    out = bench_tiny.run_cell(root, "train.tiny-b.tsfl-long", capsys,
                              trace=1)
    assert out["correct"] is True
    assert out["metrics"]["rounds_traced"]["value"] >= 1
    assert math.isfinite(out["metrics"]["train_mfu"]["value"])
    after = {p.name: p.read_bytes() for p in (root / "bench").glob("*.py")}
    assert after == before
