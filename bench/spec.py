"""What one cell of the benchmark is, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
lives in a file of its own, and so does each per-layer metric's reader:

    bench/configs/<config>.json     sizes as run, source, reduced, assumed
    bench/traffic/<traffic>.json    the mix: its path (``bench/path_<path>.py``)
                                    and the parameters that path reads
    bench/workloads/<cell>.json     what belongs to the pair: split point,
                                    limits of ``correct``
    bench/metrics/<metric>.py       one per-layer metric's reader

A cell, a configuration or a metric is added by adding files; nothing here
or in the harness changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from common import BENCH, ROOT

READER_ATTRS = ("LAYER", "MOVES", "read")


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(kind: str, name: str, bench: Path = BENCH) -> dict:
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} file {path} for {name!r} is missing")
    return json.loads(path.read_text())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, checked."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    bdir = root / "bench"
    config = _json("configs", entry["config"], bdir)
    traffic = _json("traffic", entry["traffic"], bdir)
    cell = _json("workloads", name, bdir)
    if not (bdir / f"path_{traffic.get('path')}.py").is_file():
        raise ValueError(f"traffic {entry['traffic']!r}: no bench/path_"
                         f"{traffic.get('path')}.py for its path")
    for key in ("config", "traffic"):
        if cell.get(key) != entry[key]:
            raise ValueError(f"bench/workloads/{name}.json says {key} "
                             f"{cell.get(key)!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    return {**cell, "name": name, "chips": entry["chips"],
            "path": traffic["path"], "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"]
                           if applies(m, name)],
            "per_layer": [m for m in bench["per_layer"] if applies(m, name)]}


def load_reader(name: str, root: Path = ROOT):
    """The reader module of per-layer metric ``name``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [a for a in READER_ATTRS if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"reader {path} lacks {missing}")
    return mod


def arch_for(config: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's numbers: a cell runs exactly what its file states."""
    from repro.configs import get_arch

    arch = get_arch(config["arch"])
    pairs = {"n_layer": arch.num_layers, "n_embd": arch.d_model,
             "n_head": arch.num_heads, "n_inner": arch.d_ff,
             "vocab_size": arch.vocab_size, "n_positions": arch.max_seq_len,
             "layer_norm_epsilon": arch.norm_eps,
             "lora_rank": arch.lora_rank, "lora_alpha": arch.lora_alpha,
             "lora_targets": list(arch.lora_targets)}
    bad = {k: (config[k], v) for k, v in pairs.items() if config[k] != v}
    if bad or not arch.tie_embeddings or arch.mlp_kind != "gelu_mlp":
        raise ValueError(f"config {config['arch']}: file and program differ "
                         f"(file, program): {bad}")
    return arch
