#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` in a cell of the
DeepSeek-V3 block's path (``path_train_mla_moe``), at the cell's own
size, on the chip.  The benchmark's own runs never run this.

    python bench/control_mla_moe.py --workload train.moonlight-ep8.sfl \\
        --seeds 1,2,3 [--controls 2]

For each seed the program's set-up (its first global round through the
trainer's own call, as a run makes it) gives the program's numbers.  For
the first ``--controls`` seeds (all by default) the same reference then
goes in its place three more times: one precision step down (every array
in bfloat16: the control), with half of every step's batch left out, and
with a capacity dispatch of 1.25 that drops rows (two planted faults).
Each is compared, as a run compares the program, with the plain
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


def train(cell, seed, controls: bool = True):
    import gc

    import jax.numpy as jnp

    import path_train_mla_moe as path
    from control import _State, _values

    s = path.first_round(cell, seed)
    prog, start, params = s["first"], s["start"], s["params"]
    del s
    gc.collect()
    truth = path.reference_round(cell, seed, params, start)
    lim = cell["limits"]
    out = {"program": _values(path.compare(prog, truth, start, lim))}
    for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                     ("fault_half_batch", {"half_batch": True}),
                     ("fault_capacity", {"capacity": True}))[:3 * controls]:
        r = path.reference_round(cell, seed, params, start, **kw)
        first = {"losses": r["losses"], "rows": r["rows"],
                 "state": _State(r)}
        out[name] = _values(path.compare(first, truth, start, lim))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=None,
                    help="run the controls for the first n seeds only")
    args = ap.parse_args()

    import gc

    import jax

    import spec

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    n = len(seeds) if args.controls is None else args.controls
    for i, seed in enumerate(seeds):
        r = train(cell, seed, controls=i < n)
        print(json.dumps({"workload": cell["name"], "seed": seed, **r}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
