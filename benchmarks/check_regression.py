"""Bench-regression gate: fresh BENCH_*.json vs committed baselines.

The gate compares *within-run ratios*: each gate divides a row by its
in-run reference row, computes the same ratio from the committed snapshot
under ``benchmarks/baselines/``, and fails when the fresh ratio has
regressed by more than ``--threshold`` (default 15%).  Every gated row is
counted in deterministic units (engine steps, tokens, milli-loss, model
seconds) except ``bcd_memoized``, which times the resource allocator: host
code, on the host CPU it really runs on.  Device speed is not gated here;
``bench/`` measures it on the chip.

A gate that trips does not fail immediately: the checker re-runs the
owning benchmark suite (up to ``--retries`` times) and keeps the best
fresh ratio — a real regression reproduces on every run, contention on a
shared runner does not.

Usage (CI runs this right after ``python -m benchmarks.run``):

    python benchmarks/check_regression.py [--threshold 0.15]
    python benchmarks/check_regression.py --update   # refresh baselines
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

# (snapshot file, gate id, row, in-run reference row).
# ratio = row / reference, lower is better; the gate fails when
# fresh_ratio > baseline_ratio * (1 + threshold).
GATES = [
    (
        # p99 TTFT under the Poisson trace at equal HBM: both rows are in
        # deterministic step units, so this ratio is noise-free — it
        # catches any erosion of the paged engine's admission advantage
        "BENCH_traffic.json",
        "paged_ttft_p99",
        "traffic/ttft_p99_paged",
        "traffic/ttft_p99_slab",
    ),
    (
        # allocator wall time with the memoized sweep/pair grid vs cold:
        # host code, timed on the host that runs it
        "BENCH_resource.json",
        "bcd_memoized",
        "resource/bcd_wall_memoized",
        "resource/bcd_wall_cold",
    ),
    (
        # prefix tokens recomputed per token delivered under the fixed
        # chaos schedule: both rows are deterministic counts, so the
        # ratio is noise-free — it catches recovery regressions that
        # recompute more than a preemption strictly requires
        "BENCH_faults.json",
        "fault_recompute_cost",
        "faults/tokens_recomputed",
        "faults/tokens_delivered",
    ),
    (
        # engine steps to drain the chaos trace vs the fault-free trace
        # (deterministic step counts): preemption must not stretch the
        # schedule beyond the recompute work itself
        "BENCH_faults.json",
        "fault_step_overhead",
        "faults/steps_chaos",
        "faults/steps_clean",
    ),
    (
        # defended final eval loss vs the clean run's, both deterministic
        # milli-loss rows from the same fixed episode: the ratio is
        # noise-free and catches any erosion of the robust aggregation +
        # quarantine defense (baseline ~1.0 — the defense fully tracks
        # the clean trajectory)
        "BENCH_byzantine.json",
        "byzantine_defended_loss",
        "byzantine/loss_defended_milli",
        "byzantine/loss_clean_milli",
    ),
    (
        # attacker-rounds participated / attacker-rounds total under the
        # fixed attack schedule (deterministic counts): catches a
        # detection regression that lets attackers stay in the average
        # longer before quarantine engages
        "BENCH_byzantine.json",
        "byzantine_attacker_exposure",
        "byzantine/attacker_exposure",
        "byzantine/attacker_rounds_total",
    ),
    (
        # engine steps to drain the mixed-tenant workload in ONE batched
        # engine vs per-tenant sequential engines at equal HBM — both
        # deterministic step counts, so the ratio is noise-free; it
        # catches any erosion of the mixed-batch throughput win
        "BENCH_multitenant.json",
        "multitenant_mixed_throughput",
        "multitenant/steps_mixed",
        "multitenant/steps_sequential",
    ),
    (
        # per-client BCD modeled delay with the bits axis enabled vs the
        # pre-precision search on the same edge scenario — both rows are
        # deterministic model seconds, so the ratio is noise-free; it
        # catches any erosion of the delay the quantized boundary buys
        "BENCH_precision.json",
        "precision_delay_gain",
        "precision/delay_bits_opt",
        "precision/delay_bits16",
    ),
    (
        # final eval loss of the int8-boundary episode vs the f32 run of
        # the SAME fixed episode (deterministic milli-loss rows): the gate
        # holds the paper-level claim that the quantized boundary is
        # convergence-neutral (baseline ~1.0)
        "BENCH_precision.json",
        "precision_quant_loss",
        "precision/loss_quant_milli",
        "precision/loss_f32_milli",
    ),
]


# which benchmarks.run suite regenerates each snapshot (for gate retries)
SUITE_FOR_FILE = {
    "BENCH_traffic.json": "traffic",
    "BENCH_resource.json": "resource",
    "BENCH_faults.json": "faults",
    "BENCH_byzantine.json": "byzantine",
    "BENCH_multitenant.json": "multitenant",
    "BENCH_precision.json": "precision",
}


def _load_rows(path: Path) -> dict[str, float]:
    data = json.loads(path.read_text())
    return {r["name"]: float(r["us_per_call"]) for r in data["rows"]}


def _rerun_suite(fname: str, fresh_dir: Path) -> None:
    env = dict(os.environ)
    path = env.get("PYTHONPATH")
    env["PYTHONPATH"] = "src" + os.pathsep + path if path else "src"
    subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", SUITE_FOR_FILE[fname]],
        cwd=fresh_dir,
        env=env,
        check=True,
        stdout=subprocess.DEVNULL,
    )


def _ratio(rows: dict[str, float], num: str, den: str, where: str) -> float:
    for name in (num, den):
        if name not in rows:
            raise SystemExit(f"gate row {name!r} missing from {where}")
    if rows[den] <= 0:
        raise SystemExit(f"non-positive reference row {den!r} in {where}")
    return rows[num] / rows[den]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh-dir", default=".", help="where benchmarks.run wrote BENCH_*.json")
    ap.add_argument("--baseline-dir", default="benchmarks/baselines")
    ap.add_argument("--threshold", type=float, default=0.15, help="max allowed relative slowdown")
    ap.add_argument("--retries", type=int, default=2, help="suite re-runs before a gate may fail")
    ap.add_argument("--update", action="store_true", help="copy fresh snapshots over the baselines")
    args = ap.parse_args()

    fresh_dir, base_dir = Path(args.fresh_dir), Path(args.baseline_dir)
    if args.update:
        base_dir.mkdir(parents=True, exist_ok=True)
        for fname in sorted({g[0] for g in GATES}):
            src = fresh_dir / fname
            if not src.exists():
                raise SystemExit(f"--update: {src} missing; run benchmarks.run first")
            shutil.copy(src, base_dir / fname)
            print(f"baseline updated: {base_dir / fname}")
        return 0

    failures = []
    for fname, gate_id, num, den in GATES:
        fresh_path, base_path = fresh_dir / fname, base_dir / fname
        if not fresh_path.exists():
            raise SystemExit(f"fresh snapshot {fresh_path} missing; run benchmarks.run first")
        if not base_path.exists():
            print(f"[{gate_id}] SKIP: no committed baseline {base_path}")
            continue
        base = _ratio(_load_rows(base_path), num, den, str(base_path))
        fresh = _ratio(_load_rows(fresh_path), num, den, str(fresh_path))
        attempts = 0
        while fresh / base - 1.0 > args.threshold and attempts < args.retries:
            attempts += 1
            print(
                f"[{gate_id}] tripped ({fresh / base - 1.0:+.1%}); "
                f"re-running {SUITE_FOR_FILE[fname]} ({attempts}/{args.retries})"
            )
            _rerun_suite(fname, fresh_dir)
            fresh = min(fresh, _ratio(_load_rows(fresh_path), num, den, str(fresh_path)))
        slowdown = fresh / base - 1.0
        status = "FAIL" if slowdown > args.threshold else "ok"
        print(
            f"[{gate_id}] {status}: ratio {num}/{den} "
            f"fresh={fresh:.3f} baseline={base:.3f} ({slowdown:+.1%})"
        )
        if slowdown > args.threshold:
            failures.append(
                f"  [{gate_id}] suite={SUITE_FOR_FILE[fname]} ({fname}): "
                f"{num}/{den} regressed {slowdown:+.1%} past the "
                f"{args.threshold:.0%} threshold "
                f"(fresh={fresh:.3f} vs baseline={base:.3f})"
            )

    if failures:
        print(
            f"\nbench regression gate FAILED ({len(failures)} gate(s)):\n"
            + "\n".join(failures),
            file=sys.stderr,
        )
        return 1
    print("\nbench regression gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
