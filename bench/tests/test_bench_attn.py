"""The reader of ``attn_kernel_ms``: the named training attention kernels'
device time per round, on hand-made events and on the trace recorded on a
TPU v5e before the kernels existed (bench/testdata/train_trace.json.gz),
where it must read nothing."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import gzip
import json

import pytest

import spec
import trace_reduce as tr
from common import BENCH

R = "jit__train_round_part(7)"
CELL = {"config": {"n_layer": 2}, "traffic": {"local_steps": 3}}
NAMES = {"attn_fwd": "jvp_attn_fwd_", "attn_bwd": "transpose_jvp_attn_bwd__",
         "attn_dkv": "transpose_jvp_attn_dkv__",
         "attn_dq": "transpose_jvp_attn_dq__"}
SPLIT = {"attn_fwd": 6, "attn_dkv": 6, "attn_dq": 6}


def _events(rounds=2, per_round=None, dur=2.0):
    """``rounds`` programs of 100 ns; in each, ``per_round[k]`` operations
    of kernel k (default the fused backward: I x L = 6 forward and 6
    backward), ``dur`` ns apiece, beside LoRA kernels and fusions; one
    attention op outside the round's program."""
    per_round = per_round or {"attn_fwd": 6, "attn_bwd": 6}
    ev = [["host", tr.WINDOW_SPAN, 0.0, 1000.0, ""]]
    for r in range(rounds):
        s = 200.0 * r
        ev += [["module", R, s, 100.0, R], ["op", "fusion.1", s, 10.0, R],
               ["op", "closed_call.3", s + 10, 5.0, R]]
        t = s + 20
        for k, n in per_round.items():
            for i in range(n):
                ev.append(["op", f"{NAMES[k]}.{i + 1}", t, dur, R])
                t += dur
    ev += [["module", "jit_prefill(9)", 900.0, 50.0, "jit_prefill(9)"],
           ["op", "attn_fwd.1", 900.0, 40.0, "jit_prefill(9)"]]
    return ev


def _read(ev, cell=CELL):
    return spec.load_reader("attn_kernel_ms").read({"events": ev,
                                                    "cell": cell})


def test_right_counts_read_the_time_per_round():
    # fused backward: 2 kernels x 6 ops x 2 ns per round, in ms
    assert _read(_events()) == pytest.approx(24.0 * 1e-6)
    assert _read(_events(rounds=3, dur=4.0)) == pytest.approx(48.0 * 1e-6)
    # split backward: 3 kernels x 6 ops
    assert _read(_events(per_round=SPLIT)) == pytest.approx(36.0 * 1e-6)


@pytest.mark.parametrize("per_round", [
    {"attn_fwd": 6, "attn_dkv": 6, "attn_dq": 5},      # a layer without dQ
    {"attn_fwd": 12, "attn_dkv": 6, "attn_dq": 6},     # the forward twice
    {"attn_fwd": 6, "attn_dkv": 6},                    # no dQ kernel at all
    {"attn_fwd": 6, "attn_bwd": 5},                    # a layer's backward
    {"attn_fwd": 6, "attn_bwd": 6, "attn_dq": 6},      # both backwards
    {"attn_bwd": 6},                                   # no forward
    {},                                                # the jnp path
])
def test_wrong_counts_read_nothing(per_round):
    ev = _events(per_round=per_round or {"attn_fwd": 0})
    assert _read(ev) is None


def test_no_round_reads_nothing():
    ev = [e for e in _events() if e[4] != R]
    assert _read(ev) is None


def test_recorded_parent_trace_reads_nothing():
    rec = json.load(gzip.open(BENCH / "testdata/train_trace.json.gz", "rt"))
    cell = {"config": {"n_layer": 12}, "traffic": {"local_steps": 12}}
    assert tr.module_runs(rec["events"], "_train_round_part")
    assert _read(rec["events"], cell) is None
