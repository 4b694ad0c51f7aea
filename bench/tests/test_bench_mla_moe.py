"""The DeepSeek-V3 block's benchmark path (``path_train_mla_moe``): its
configuration file is what the program runs and its weights are in the
program's layout; at a tiny size on the CPU a whole traced run is
``correct`` while the bfloat16 control and the planted faults (half a
batch, a capacity dispatch that drops rows) fail the limits; the four
readers read their scopes, kernels and the routed-rows counter on
hand-made events, and nothing where those are absent, as on the trace
recorded on a TPU v5e of a GPT-2 round (bench/testdata/
train_trace.json.gz)."""
import bench_tiny  # noqa: F401 — puts bench/ and src/ on the path
import gzip
import json
import math

import jax
import numpy as np
import pytest

import flops_mla_moe
import path_train_mla_moe as path
import spec
import trace_reduce as tr
from common import BENCH

CELL = "train.mtiny.tsfl-mla"
R = "jit__train_round_part(3)"
METRICS = ("train_mfu.moe", "moe_gmm_roofline.train", "moe_route_ms",
           "mla_attn_roofline.train")
# the program's CPU path computes in float32, so its gaps to the
# reference are rounding; the control and the faults read far above
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-3, "update_gap": 1e-2,
          "route_flip": 1e-3}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _real_config():
    return json.loads((BENCH / "configs/moonlight-ep8.json").read_text())


def _tiny_arch():
    from repro.configs import get_arch
    return get_arch("moonlight-16b-a3b").replace(
        name="mtiny", d_model=256, num_heads=4, num_kv_heads=4, d_ff=512,
        kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, num_experts=8, experts_per_token=2, moe_d_ff=64,
        shared_d_ff=128, max_seq_len=256)


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A benchmark root with a tiny cell of this path: a leading dense
    layer and 2 MoE layers of width 256, experts 4-7 of 8 held, top-2,
    vocabulary 512, 2 clients x 1 x 32 tokens, 2 local steps."""
    import repro.configs as rc

    bench_tiny.patch(monkeypatch)
    real = rc.get_arch
    monkeypatch.setattr(rc, "get_arch",
                        lambda n: _tiny_arch() if n == "mtiny" else real(n))
    root = bench_tiny.make_root(tmp_path)
    b = root / "bench"
    cfg = _real_config()
    cfg.update(arch="mtiny", hidden_size=256, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=512, kv_lora_rank=64,
               qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
               moe_intermediate_size=64, num_experts_per_tok=2,
               num_hidden_layers=3, n_routed_experts=4, vocab_size=512,
               max_position_embeddings=256,
               deployment={"chips_per_layer": 2, "router_experts": 8,
                           "first_expert": 4})
    (b / "configs/mtiny.json").write_text(json.dumps(cfg))
    t = json.loads((b / "traffic/sfl-s2048.json").read_text())
    t.update(seq_len=32, local_steps=2, clients=2)
    (b / "traffic/tsfl-mla.json").write_text(json.dumps(t))
    (b / f"workloads/{CELL}.json").write_text(json.dumps(
        {"config": "mtiny", "traffic": "tsfl-mla", "split_layer": 1,
         "limits": LIMITS}))
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": CELL, "config": "mtiny",
                            "traffic": "tsfl-mla", "chips": 1, "why": "x"})
    for m in bj["end_to_end"] + bj["per_layer"]:
        if m["name"] in METRICS + ("train_tokens_per_s",):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    return root


# ---------------------------------------------------------------------------
# the configuration and the weights
# ---------------------------------------------------------------------------

def test_config_file_is_what_the_program_runs():
    cfg = _real_config()
    arch = path.arch_for(cfg)
    assert (arch.num_layers, arch.held_experts, arch.num_experts,
            arch.vocab_size) == (6, 8, 64, 20480)
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert all(cfg["published"][k] != cfg[k] for k in cfg["reduced"])
    for key, wrong in (("hidden_size", 1024), ("scoring_func", "softmax"),
                       ("n_shared_experts", 1), ("rms_norm_eps", 1e-6)):
        with pytest.raises(ValueError):
            path.arch_for(dict(cfg, **{key: wrong}))


def test_weights_are_in_the_programs_layout():
    """At the cell's own size (shapes only): the benchmark's weights and
    adapters have the tree, shapes and dtypes the program's own init
    makes."""
    import weights_mla_moe as w
    from repro.models.model import init_lora_stack, init_params

    cfg = _real_config()
    arch = path.arch_for(cfg)
    key = jax.random.key(0)
    for ours, theirs in (
            (lambda: w.make_params(cfg, key), lambda: init_params(arch, key)),
            (lambda: w.make_lora(cfg, key),
             lambda: init_lora_stack(arch, key))):
        a, b = jax.eval_shape(ours), jax.eval_shape(theirs)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        assert jax.tree.leaves(a) == jax.tree.leaves(b)


# ---------------------------------------------------------------------------
# correct, the control and the faults
# ---------------------------------------------------------------------------

def test_traced_run_is_correct(root, capsys):
    """A whole traced run: ``correct``, the counter-based MFU read, and
    the round's compiled text maps instructions to the routing scopes;
    the device readers read nothing without a TPU in the trace."""
    out = bench_tiny.run_cell(root, CELL, capsys, trace=1)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "update_gap",
                                  "route_flip"}
    assert math.isfinite(out["metrics"]["train_mfu.moe"]["value"])
    assert not set(METRICS[1:]) & set(out["metrics"])


def test_control_and_faults_fail_the_limits(root):
    import control_mla_moe

    cell = spec.load_cell(CELL, root)
    got = control_mla_moe.train(cell, 2**40 + 11)
    assert got["program"]["passed"] is True, got
    for name in ("control_bf16", "fault_half_batch", "fault_capacity"):
        assert got[name]["passed"] is False, (name, got)
    assert got["fault_capacity"]["route_flip"] > 10 * LIMITS["route_flip"]
    assert got["control_bf16"]["update_gap"] > LIMITS["update_gap"]


def test_route_flip_counts_rows_that_differ():
    ref = np.array([[[3, 5]], [[4, 4]]])
    assert path.route_flip(ref, ref) == 0.0
    assert path.route_flip([[[2, 6]], [[4, 4]]], ref) == pytest.approx(2 / 16)
    assert path.route_flip([[[3, 5]], [[4, 3]]], ref) == pytest.approx(1 / 16)
    assert path.route_flip(ref[:1], ref) == math.inf


def test_scope_instructions_reads_op_names():
    text = "\n".join([
        'HloModule m',
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(r)/while/body/moe.route/argsort" '
        'source_file="x.py"}',
        '  sort.2 = s32[4]{0} sort(%a), metadata={op_name='
        '"jit(r)/transpose(jvp(moe.combine))/gather"}',
        '  ROOT %tuple.1 = (f32[4]) tuple(%x), metadata={op_name="jit(r)/'
        'moe.experts/moe_gmm_fwd"}',
        '  %copy.1 = f32[4]{0} copy(%x)'])
    assert path.scope_instructions(text) == ["fusion.3", "sort.2"]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _cfg():
    return _real_config()


def _cell(I=2):
    return {"config": _cfg(), "traffic": {"local_steps": I, "clients": 5,
                                          "seq_len": 2048}}


def _rows(rounds=2, I=2, layers=5):
    return [np.full((I, layers, 8), 120).tolist() for _ in range(rounds)]


def _events(rounds=2, per_round=None, dur=2.0, extra=()):
    """``rounds`` round programs of 10 us; in each, ``per_round[name]``
    operations of that name, ``dur`` ns apiece; one more of each outside
    the round's program."""
    ev = [["host", tr.WINDOW_SPAN, 0.0, 1e6, ""]]
    for r in range(rounds):
        s = 2e4 * r
        ev.append(["module", R, s, 1e4, R])
        t = s + 10
        for name, n in (per_round or {}).items():
            for i in range(n):
                ev.append(["op", f"{name}.{i + 1}" if "." not in name
                           else name, t, dur, R])
                t += dur
    ev += [["module", "jit_other(1)", 9e5, 50.0, "jit_other(1)"]]
    ev += [["op", n, 9e5, 40.0, "jit_other(1)"] for n in extra]
    return ev


def _read(metric, ev, run=None, cell=None):
    ctx = {"events": ev, "run": run or {}, "cell": cell or _cell(),
           "peaks": PEAKS,
           "summary": {"busy_s": 0.0, "window_s": 0.0}}
    return spec.load_reader(metric).read(ctx)


def test_gmm_roofline_reads_the_counted_rows():
    # 2 rounds x 2 steps x 5 layers: 6 and 3 kernel calls each
    ev = _events(per_round={"jvp_moe_gmm_fwd_": 60, "moe_gmm_dx": 30},
                 dur=1e6)
    run = {"expert_rows": _rows()}
    calls = flops_mla_moe.expert_swiglu_calls(960, _cfg())
    least = 20 * sum(flops_mla_moe.roofline_s(o, b, PEAKS)
                     for c in calls.values() for o, b in c)
    got = _read("moe_gmm_roofline.train", ev, run)
    assert got == pytest.approx(100 * least / (180 * 1e6 * 1e-9))
    # a call missing, a round without its counts, no counter, GPT-2
    for e, r, c in (
            (_events(per_round={"moe_gmm_fwd": 60, "moe_gmm_dx": 29}), run,
             None),
            (ev, {"expert_rows": _rows(rounds=1)}, None),
            (ev, {}, None),
            (ev, run, {"config": {"n_layer": 2}, "traffic": {}})):
        assert _read("moe_gmm_roofline.train", e, r, c) is None


@pytest.mark.parametrize("backward", [{"attn_bwd": 12},
                                      {"attn_dkv": 12, "attn_dq": 12}])
def test_mla_attention_roofline_reads_its_kernels(backward):
    # 2 rounds x 2 steps x 6 layers
    ev = _events(per_round={"jvp_attn_fwd_": 12, **backward}, dur=1e6,
                 extra=("attn_fwd.9",))
    fwd, bwd = flops_mla_moe.attention_calls(5, 2048, _cfg())
    least = 24 * (flops_mla_moe.roofline_s(*fwd, PEAKS)
                  + flops_mla_moe.roofline_s(*bwd, PEAKS))
    busy = (24 + 24 * len(backward)) * 1e6 * 1e-9
    assert _read("mla_attn_roofline.train", ev) == pytest.approx(
        100 * least / busy)


@pytest.mark.parametrize("per_round", [
    {"attn_fwd": 11, "attn_bwd": 12},                  # a layer's forward
    {"attn_fwd": 12, "attn_bwd": 12, "attn_dq": 12},   # both backwards
    {"attn_bwd": 12},                                  # no forward
    {},                                                # the jnp path
])
def test_mla_attention_roofline_wrong_counts_read_nothing(per_round):
    assert _read("mla_attn_roofline.train", _events(per_round=per_round)) \
        is None


def test_route_ms_reads_the_mapped_instructions():
    names = ["fusion.7", "sort.2"]
    ev = _events(per_round={"fusion.7": 1, "sort.2": 1, "fusion.8": 1},
                 dur=5e5, extra=("fusion.7",))
    run = {"scope_instructions": names}
    assert _read("moe_route_ms", ev, run) == pytest.approx(1.0)
    assert _read("moe_route_ms", ev, {}) is None
    assert _read("moe_route_ms", ev, {"scope_instructions": ["x.1"]}) is None
    assert _read("moe_route_ms", _events(rounds=0), run) is None


def test_mfu_reads_the_counted_rows():
    cell = _cell(I=12)
    rows = [np.full((12, 5, 8), 960).tolist()]           # 0.75 a token
    run = {"tokens": 122880, "window_s": 4.0, "expert_rows": rows}
    per_tok = flops_mla_moe.train_flops_per_token(_cfg(), 2048, 0.75)
    assert _read("train_mfu.moe", [], run, cell) == pytest.approx(
        100 * per_tok * 122880 / 4.0 / PEAKS["bf16_flops_per_s"])
    assert 1.35e9 < per_tok < 1.5e9
    assert _read("train_mfu.moe", [], dict(run, expert_rows=[]), cell) is None
    gpt2 = {"config": {"n_layer": 2}, "traffic": {"seq_len": 8}}
    assert _read("train_mfu.moe", [], run, gpt2) is None


def test_recorded_gpt2_trace_reads_nothing():
    rec = json.load(gzip.open(BENCH / "testdata/train_trace.json.gz", "rt"))
    cell = {"config": {"n_layer": 12, "n_embd": 768, "lora_rank": 4,
                       "batch_size": 3},
            "traffic": {"local_steps": 12, "clients": 5, "seq_len": 512}}
    run = {"tokens": 1, "window_s": 1.0, "rounds": 3}
    assert tr.module_runs(rec["events"], "_train_round_part")
    for metric in METRICS:
        assert _read(metric, rec["events"], run, cell) is None, metric
