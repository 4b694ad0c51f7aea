# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  table3  bench_complexity   GPT2-S params/FLOPs with LoRA
  table4  bench_ppl          centralized vs SflLLM perplexity
  fig3/4  bench_convergence  loss curves + steps-to-target per rank (+E(r) fit)
  fig5-8  bench_latency      latency sweeps, proposed vs baselines a-d
  traffic bench_traffic      paged vs slab KV: Poisson TTFT in engine steps
  roofline bench_roofline    per (arch x shape x mesh) roofline rows
  resource bench_resource    BCD wall time + homogeneous-vs-hetero delay
  dynamic bench_dynamic      adaptive re-allocation over a fading episode
  faults  bench_faults       failure-recovery cost: preemption recompute + rollback
  byzantine bench_byzantine  attacker damage vs robust-aggregation defense
  multitenant bench_multitenant  mixed-tenant engine vs sequential engines
  precision bench_precision  bits-axis delay gain + int8-boundary episode loss

Rows are the paper's tables and figures, in model seconds, step counts,
losses and allocator wall time.  Device speed is not measured here: it is
measured on the chip by ``bench/``.

Usage: PYTHONPATH=src python -m benchmarks.run [--only table4,fig5 ...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from . import (bench_byzantine, bench_complexity, bench_convergence,
               bench_dynamic, bench_faults, bench_latency, bench_multitenant,
               bench_ppl, bench_precision, bench_resource, bench_roofline,
               bench_traffic)

SUITES = {
    "table3": bench_complexity.main,
    "table4": bench_ppl.main,
    "convergence": bench_convergence.main,
    "latency": bench_latency.main,
    "traffic": bench_traffic.main,
    "roofline": bench_roofline.main,
    "resource": bench_resource.main,
    "dynamic": bench_dynamic.main,
    "faults": bench_faults.main,
    "byzantine": bench_byzantine.main,
    "multitenant": bench_multitenant.main,
    "precision": bench_precision.main,
}

# snapshots: these row prefixes land in JSON files CI archives per commit
# (and checks against benchmarks/baselines/ via
# benchmarks/check_regression.py)
SNAPSHOTS = {
    "BENCH_traffic.json": ("traffic/",),
    "BENCH_resource.json": ("resource/",),
    "BENCH_dynamic.json": ("dynamic/",),
    "BENCH_faults.json": ("faults/",),
    "BENCH_byzantine.json": ("byzantine/",),
    "BENCH_multitenant.json": ("multitenant/",),
    "BENCH_precision.json": ("precision/",),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of " + ",".join(SUITES))
    args = ap.parse_args()
    picked = [s.strip() for s in args.only.split(",") if s.strip()] or \
        list(SUITES)

    print("name,us_per_call,derived")
    rows = []

    def emit(name, us, derived):
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()
        rows.append({"name": name, "us_per_call": round(float(us), 1),
                     "derived": str(derived)})

    failed = []
    for name in picked:
        t0 = time.time()
        try:
            SUITES[name](emit)
            emit(f"{name}/_suite_wall", (time.time() - t0) * 1e6, "ok")
        except Exception as e:  # noqa: BLE001 — run the rest, fail at exit
            traceback.print_exc()
            failed.append(name)
            emit(f"{name}/_suite_wall", (time.time() - t0) * 1e6,
                 f"FAILED:{e!r}")

    for fname, prefixes in SNAPSHOTS.items():
        picked_rows = [r for r in rows if r["name"].startswith(prefixes)]
        if not picked_rows:
            continue
        with open(fname, "w") as f:
            json.dump({"unix_time": int(time.time()), "rows": picked_rows},
                      f, indent=2)
        print(f"wrote {fname} ({len(picked_rows)} rows)", file=sys.stderr)
    if failed:
        raise SystemExit(f"{len(failed)} suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
