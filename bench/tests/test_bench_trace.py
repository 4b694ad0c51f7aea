"""The reduction from trace to metrics, on a trace recorded on a TPU v5e
(bench/testdata/serve_trace.json.gz: the paged multi-tenant engine
admitting one prompt and running four decode steps) and on hand-made
events."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import gzip
import json

import pytest

import trace_reduce as tr
from common import BENCH

REC = json.load(gzip.open(BENCH / "testdata/serve_trace.json.gz", "rt"))
EV = REC["events"]


def test_recorded_trace_programs_and_kernels():
    assert len(tr.module_runs(EV, "_step_paged")) == 4
    assert len(tr.module_runs(EV, "jit__chunk")) == 3
    # two gather kernels per layer per decode step, one fused LoRA forward
    # per adapted projection per chunk, a paged attention kernel per layer
    assert len(tr.ops_named(EV, "closed_call", "_step_paged", exact=True)) == 96
    assert len(tr.ops_named(EV, "closed_call", "jit__chunk", exact=True)) == 72
    assert len(tr.ops_named(EV, "paged_decode", "_step_paged", exact=True)) == 48


def test_recorded_trace_summary():
    s = tr.summary(EV, 1.0)
    lo, hi = tr.window_bounds(EV)
    assert (hi - lo) * 1e-9 == pytest.approx(s["window_s"])
    assert 0 < s["busy_s"] <= s["window_s"]
    ops = s["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, key=lambda o: -o[1])
    assert sum(d for _, d in ops) <= s["busy_s"] * 1.0001
    gaps = s["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10 and all(isinstance(n, str) for n, _ in gaps)
    busy = s["busy_s"] + sum(e - b for b, e in tr.idle_gaps(EV, lo, hi)) * 1e-9
    assert busy == pytest.approx(s["window_s"])


def test_hand_made_events():
    ev = [["host", tr.WINDOW_SPAN, 0.0, 100.0, ""],
          ["module", "jit_step(1)", 10.0, 20.0, "jit_step(1)"],
          ["op", "while.3", 10.0, 20.0, "jit_step(1)"],
          ["op", "fusion.1", 12.0, 5.0, "jit_step(1)"],
          ["op", "fusion.2", 20.0, 5.0, "jit_step(1)"],
          ["module", "jit_step(1)", 50.0, 10.0, "jit_step(1)"],
          ["op", "closed_call.7", 50.0, 10.0, "jit_step(1)"],
          ["op", "fusion.9", 55.0, 20.0, "jit_other(2)"],
          ["host", "admit", 30.0, 15.0, ""]]
    assert tr.busy(ev, 0, 100) == [[10.0, 30.0], [50.0, 75.0]]
    assert tr.idle_gaps(ev, 0, 100) == [(0, 10.0), (30.0, 50.0), (75.0, 100)]
    runs = tr.module_runs(ev, "jit_step")
    assert runs == [(10.0, 30.0), (50.0, 60.0)]
    assert tr.gaps_between(ev, runs, 0, 100) == [20.0]
    own = tr.self_times(ev, 0, 100)
    assert own == {"while": 10.0, "fusion": 30.0, "closed_call": 10.0}
    assert tr.host_at(ev, 40.0) == "admit"
    assert tr.host_at(ev, 90.0) == "host idle"
    assert tr.op_kind("fusion.12") == "fusion" and tr.op_kind("while") == "while"
