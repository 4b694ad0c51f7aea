"""A small copy of the benchmark for CPU tests: the real ``bench`` files
plus a tiny training cell (a 2-layer GPT-2 of width 64 and vocabulary
512), under a temporary root that ``run.main`` and ``spec`` read."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRAIN = "train.tiny.tsfl"
# the program's CPU path computes in float32, so its gaps to the
# reference are rounding; these limits sit far below what the bfloat16
# control reads at this size (tests/test_bench_correct.py)
TRAIN_LIMITS = {"loss_gap": 1e-6, "grad_gap": 1e-3, "update_gap": 1e-3}


def tiny_arch():
    from repro.configs import get_arch
    return get_arch("gpt2-s").reduced(num_layers=2, d_model=64, vocab=512)


def patch(monkeypatch):
    """The tiny architecture under the name "tiny", and peaks for the CPU."""
    import repro.configs as rc

    import common

    real = rc.get_arch
    monkeypatch.setattr(rc, "get_arch",
                        lambda n: tiny_arch() if n == "tiny" else real(n))
    monkeypatch.setattr(common, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _dump(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path) -> Path:
    root = tmp / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    b = root / "bench"
    cfg = json.loads((b / "configs/gpt2-s.json").read_text())
    cfg.update(arch="tiny", n_layer=2, n_embd=64, n_head=4, n_inner=128,
               vocab_size=512, n_positions=256, batch_size=2)
    _dump(b / "configs/tiny.json", cfg)
    tr = json.loads((b / "traffic/sfl.json").read_text())
    tr.update(seq_len=32, local_steps=3, clients=2)
    _dump(b / "traffic/tsfl.json", tr)
    _dump(b / f"workloads/{TRAIN}.json",
          {"config": "tiny", "traffic": "tsfl", "split_layer": 1,
           "limits": TRAIN_LIMITS})
    bj = json.loads((ROOT / "BENCHMARK.json").read_text())
    bj["workloads"] = [{"name": TRAIN, "config": "tiny", "traffic": "tsfl",
                        "chips": 1, "why": "tiny"}]
    for m in bj["end_to_end"] + bj["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TRAIN]
    _dump(root / "BENCHMARK.json", bj)
    return root


def run_cell(root: Path, cell: str, capsys, trace: int = 0,
             seed: int = 2**40 + 7, seconds: float = 2.0) -> dict:
    """A whole run of ``cell`` without the look for a chip; its result."""
    import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  look_for_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
