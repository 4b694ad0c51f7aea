#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's own, the
control's and the planted faults', at a cell's own size, on the chip.  The
benchmark's own runs never run this.

    python bench/control.py --workload train.gpt2-s.sfl --seeds 1,2,3

The program's set-up (its first global round through the trainer's own
call, as a run makes it) gives the program's numbers; in its place then go
the same reference one precision step down (every array in bfloat16: the
control) and the reference with half of every step's batch left out (a
fault).  Each is compared, as a run compares the program, with the plain
reference in float32 at ``highest``.  A state left unchanged reads 1 on
both leaf numbers by their construction and needs no run.  Each reading
comes with ``passed``: whether the cell's limits let it through."""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


class _State:
    """A reference round's result in the shape of the program's state."""

    def __init__(self, r):
        self.lora_client, self.lora_server = r["lora_client"], r["lora_server"]
        self.opt_client = {"m": r["m_client"]}
        self.opt_server = {"m": r["m_server"]}


def _values(compared: dict) -> dict:
    import checks
    return {**{k: v["value"] for k, v in compared.items()},
            "passed": checks.passed(compared)}


def train(cell, seed):
    import gc

    import jax.numpy as jnp

    import checks
    import path_train

    s = path_train.first_round(cell, seed)
    prog, start, params = s["first"], s["start"], s["params"]
    del s
    gc.collect()
    truth = path_train.reference_round(cell, seed, params, start)
    lim = cell["limits"]
    out = {"program": _values(checks.train(prog, truth, *start, lim))}
    for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                     ("fault_half_batch", {"half_batch": True})):
        r = path_train.reference_round(cell, seed, params, start, **kw)
        first = {"losses": r["losses"], "state": _State(r)}
        out[name] = _values(checks.train(first, truth, *start, lim))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import gc

    import jax

    import spec

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = train(cell, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed, **r}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
