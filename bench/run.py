#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compile or persistent-cache load, warm-up)
is timed as ``setup_s``; then the cell's path runs for ``--seconds`` and
the end-to-end metrics are taken on the host clock (``--trace 0``), or a
profiler trace of the window is reduced to the per-layer metrics
(``--trace 1``).  After the window the plain reference checks what the
timed path produced.  The last line of standard output is one JSON object;
the numbers compared for ``correct`` are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the persistent compile cache lives at one fixed path inside the checkout,
# whatever the machine's environment says; the TPU runtime writes no logs
# outside it
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


class RunLog:
    """Set-up and window boundaries, and the compiles inside the window."""

    def __init__(self, compiles):
        self.compiles = compiles
        self.setup_s = None
        self.window_compiles = None

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_PROCESS
        self.compiles.mark()

    def window_done(self):
        self.window_compiles = self.compiles.window()


def main(argv=None, root: Path = ROOT, look_for_chip: bool = True) -> int:
    """``root`` and ``look_for_chip`` let a CPU test drive a whole run of a
    small cell; the benchmark itself runs with the defaults."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import checks
    import spec
    from common import CompileLog, device_record, peaks

    cell = spec.load_cell(args.workload, root)
    import jax

    devs = jax.devices()
    if look_for_chip and devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 2
    devs = devs[:cell["chips"]]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log = RunLog(CompileLog())
    chip = peaks(devs[0].device_kind)
    readers = ({m["name"]: spec.load_reader(m["name"], root)
                for m in cell["per_layer"]} if args.trace else {})
    dev = {}
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        ctx = {"trace_dir": tdir, "devices": devs, "peaks": chip,
               "device_record": lambda: dev.update(device_record(devs))}
        path = importlib.import_module(f"path_{cell['path']}")
        out = path.run(cell, args.seed, args.seconds, bool(args.trace), log,
                       ctx)
    print(f"bench: {cell['name']} seed {args.seed}: set-up "
          f"{log.setup_s:.3f}s, window {out['window_s']:.3f}s, compiles in "
          f"the window {log.window_compiles}; {log.compiles.summary()}; "
          f"cache {cache}", file=sys.stderr)
    for line in out.get("notes", []):
        print(f"bench: {line}", file=sys.stderr)

    metrics = {}
    if args.trace:
        import trace_reduce
        tr = out["trace"]
        summ = trace_reduce.summary(tr["events"], tr["host_s"])
        dev.update(busy_s=summ["busy_s"], window_s=summ["window_s"])
        rctx = {"events": tr["events"], "summary": summ, "run": out,
                "cell": cell, "peaks": chip}
        for m in cell["per_layer"]:
            v = readers[m["name"]].read(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            v = (log.setup_s if m["name"] == "setup_s"
                 else out["e2e"].get(m["name"]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": checks.passed(out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if args.trace:
        result["breakdown"] = summ["breakdown"]
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
