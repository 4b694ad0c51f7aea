"""The DeepSeek-V3 block at a tiny size (d 256, 4 heads, small latent
attention, 8 experts, top-2, a dense leading layer and 2 MoE layers):
the SFL round agrees with the plain reference (``bench/
reference_mla_moe.py``, which imports nothing of the program) in losses,
gradients, adapter updates and routed rows, and a capacity dispatch that
drops rows does not; the router picks the reference's experts;
held-expert shares add up to the uncut layer, routing drops no row under
a skewed router, the grouped-matmul kernels (interpret mode) agree with
the masked products, and the prefix boundary is a legal split that
trains through the SFL round."""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_arch
from repro.core.split import layers_to_reps, valid_splits
from repro.kernels.grouped_matmul import expert_swiglu
from repro.models import moe
from repro.models.layers import swiglu_mlp
from repro.models.model import forward, init_lora_stack, init_params


def _load_reference():
    path = Path(__file__).resolve().parents[1] / "bench/reference_mla_moe.py"
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()

ARCH = get_arch("moonlight-16b-a3b").replace(
    name="moonlight-tiny", num_layers=3, d_model=256, num_heads=4,
    num_kv_heads=4, d_ff=512, vocab_size=512, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, num_experts=8,
    experts_per_token=2, moe_d_ff=64, shared_d_ff=128, max_seq_len=256)


def _layer(cfg, key, skew=0.0):
    """One dropless MoE layer's weights for all 8 experts; ``skew`` adds
    to the first two experts' selection bias."""
    p = moe.init_moe_dropless(cfg.replace(experts_held=0), key, jnp.float32)
    bias = jax.random.normal(jax.random.fold_in(key, 9), (8,)) * 0.05
    p["router"]["bias"] = bias.at[:2].add(skew)
    p["router"]["w"] = p["router"]["w"] * 20.0           # spread the scores
    return p


def _share(p, first, n):
    return {**p, **{k: p[k][first:first + n]
                    for k in ("w_gate", "w_up", "w_down")}}


def _dense_formula(cfg, p, x):
    """Every expert on every token, weighted by its gate where chosen: the
    uncut layer with nothing dropped, written out."""
    x2 = x.reshape(-1, x.shape[-1])
    ids, w = reference.route(x2, p["router"], cfg.experts_per_token,
                             cfg.routed_scale)
    out = 0.0
    for e in range(cfg.num_experts):
        c = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1, keepdims=True)
        y = (jax.nn.silu(x2 @ p["w_gate"][e]) * (x2 @ p["w_up"][e])) \
            @ p["w_down"][e]
        out = out + c * y
    return out.reshape(x.shape) + swiglu_mlp(cfg, x, p["shared"])


def test_shares_add_up_to_the_uncut_layer():
    """Two chips' shares (experts 0-3 and 4-7), the shared expert counted
    once, give what the uncut layer gives; each share counts only its own
    experts' rows and the two counts make the uncut one."""
    cfg = ARCH
    p = _layer(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 256))
    with jax.default_matmul_precision("highest"):
        full, rows = moe.apply_moe_dropless(cfg, p, x)
        a, ra = moe.apply_moe_dropless(
            cfg.replace(experts_held=4, first_expert=0), _share(p, 0, 4), x)
        b, rb = moe.apply_moe_dropless(
            cfg.replace(experts_held=4, first_expert=4), _share(p, 4, 4), x)
        shared = swiglu_mlp(cfg, x, p["shared"])
        dense = _dense_formula(cfg, p, x)
    np.testing.assert_allclose(a + b - shared, full, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(full, dense, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.concatenate([ra, rb]), rows)
    assert int(rows.sum()) == 2 * 32 * cfg.experts_per_token


def test_skewed_router_drops_no_row():
    """Most tokens pick experts 0 and 1: far past what a capacity of 1.25x
    the even share takes, every row still reaches its expert, and the
    output and its input gradient are the dense formula's."""
    cfg = ARCH
    p = _layer(cfg, jax.random.key(2), skew=5.0)
    x = jax.random.normal(jax.random.key(3), (1, 64, 256))
    with jax.default_matmul_precision("highest"):
        out, rows = moe.apply_moe_dropless(cfg, p, x)
        dense = _dense_formula(cfg, p, x)
        g = jax.grad(lambda x: jnp.sum(
            moe.apply_moe_dropless(cfg, p, x)[0] ** 2))(x)
        g_ref = jax.grad(lambda x: jnp.sum(_dense_formula(cfg, p, x) ** 2))(x)
    cap = math.ceil(1.25 * 64 * cfg.experts_per_token / cfg.num_experts)
    assert int(rows[:2].min()) > 3 * cap           # the hot experts
    assert int(rows.sum()) == 64 * cfg.experts_per_token
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(g, g_ref, atol=1e-4, rtol=1e-4)


def test_grouped_matmul_kernels_match_the_masked_products():
    """The megablox kernels (interpret mode) against the CPU path: output,
    the input's and the gate weights' gradients; rows past the held
    experts' come out 0."""
    E, d, f, M = 2, 128, 128, 40
    ks = jax.random.split(jax.random.key(4), 6)
    xs = jax.random.normal(ks[0], (M, d))
    gate = jax.random.uniform(ks[1], (M,))
    wg, wu = (jax.random.normal(k, (E, d, f)) * d ** -0.5 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, f, d)) * f ** -0.5
    gs = jnp.array([13, 19, 8], jnp.int32)                  # 8 not held
    dy = jax.random.normal(ks[5], (M, d))

    def run(**kw):
        f_ = lambda x, g: expert_swiglu(x, g, gs, wg, wu, wd, **kw)
        y, vjp = jax.vjp(f_, xs, gate)
        return (y,) + vjp(dy)

    with jax.default_matmul_precision("highest"):
        ref = run(use_kernel=False)
        got = run(interpret=True)
    assert not np.any(np.asarray(ref[0][32:]))
    for name, a, b in zip(("y", "dx", "dgate"), got, ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


def test_prefix_boundary_is_a_legal_split_and_trains():
    """The leading dense layer is the first legal split; splits at it and
    one MoE layer later, trained through ``Trainer.fit``, give the first
    step's loss the whole model's forward gives, and the history keeps
    the held experts' row counts of each round."""
    from repro.core.sfl import SflLLM
    from repro.launch.engine import SflRound, Trainer
    from repro.optim import adamw

    cfg = ARCH.replace(experts_held=4)
    assert valid_splits(cfg) == [1, 2]
    assert layers_to_reps(cfg, 1) == 0
    with pytest.raises(ValueError):
        layers_to_reps(cfg, 0)
    params = init_params(cfg, jax.random.key(5))
    lora = init_lora_stack(cfg, jax.random.key(6))
    ids = np.random.default_rng(0).integers(0, 512, (2, 2, 1, 33), np.int32)
    toks, labels = ids[..., :32], ids[..., 1:]
    logits, _ = forward(cfg, params, jnp.asarray(toks[0].reshape(2, 32)),
                        lora=lora)
    lse = jax.nn.logsumexp(logits, -1)
    want = float(jnp.mean(lse - jnp.take_along_axis(
        logits, labels[0].reshape(2, 32)[..., None], -1)[..., 0]))
    for ell in (1, 2):
        sfl = SflLLM(cfg, params, ell_c=ell,
                     train_cfg=TrainConfig(num_clients=2, batch_size=1,
                                           local_steps=2),
                     optimizer=adamw(1e-3))
        # a fresh template: the round donates the state it is given
        state = sfl.init_state(init_lora_stack(cfg, jax.random.key(6)))
        steps = iter([{"tokens": toks[i], "labels": labels[i]}
                      for i in range(2)])
        _, hist = Trainer(SflRound(sfl, [1, 1]), local_steps=2).fit(
            state, steps, global_rounds=1)
        assert hist.losses[0] == pytest.approx(want, rel=1e-5)
        rows = np.asarray(hist.expert_rows[0])
        assert rows.shape == (2, 2, 4)                 # steps, layers, held
        assert 0 < rows.sum() <= 2 * 2 * 2 * 32 * 2


def test_published_numbers():
    """Moonlight-16B-A3B's config.json, key by key."""
    c = get_arch("moonlight-16b-a3b")
    assert (c.num_layers, len(c.prefix), c.d_model, c.num_heads,
            c.num_kv_heads, c.d_ff, c.vocab_size) == (27, 1, 2048, 16, 16,
                                                      11264, 163840)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.rope_theta, c.norm_eps) == (512, 128, 64, 128,
                                                        50000.0, 1e-5)
    assert (c.num_experts, c.experts_per_token, c.moe_d_ff, c.shared_d_ff,
            c.routed_scale, c.tie_embeddings) == (64, 6, 1408, 2 * 1408,
                                                  2.446, False)
    assert [p.mlp for p in c.layer_kinds] == ["dense"] + ["moe"] * 26


def test_router_matches_the_reference():
    """The program's router chooses the reference's experts with the
    reference's gate weights, selection bias and scale included."""
    p = _layer(ARCH, jax.random.key(7), skew=0.3)
    x = jax.random.normal(jax.random.key(8), (256, 256))
    with jax.default_matmul_precision("highest"):
        ids, w = moe.route(ARCH, p, x)
        rids, rw = reference.route(x, p["router"], ARCH.experts_per_token,
                                   ARCH.routed_scale)
    np.testing.assert_array_equal(ids, rids)
    np.testing.assert_allclose(w, rw, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.sum(w, -1), ARCH.routed_scale, rtol=1e-5)


def _ref_config(cfg):
    """The reference's configuration: DeepSeek-V3's key names, the cut's
    values."""
    return {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "first_k_dense_replace": len(cfg.prefix),
            "num_hidden_layers": cfg.num_layers,
            "n_routed_experts": cfg.held_experts,
            "num_experts_per_tok": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scale,
            "lora_rank": cfg.lora_rank, "lora_alpha": cfg.lora_alpha,
            "deployment": {"first_expert": cfg.first_expert,
                           "router_experts": cfg.num_experts}}


def _jitter(tree, key):
    """Norm scales, the router's selection bias and the adapters' B off
    their starting values, so that each one's use is checked."""
    leaves, tdef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for i, (path, v) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        n = jax.random.normal(jax.random.fold_in(key, i), v.shape, v.dtype)
        std = {"['scale']": 0.1, "['bias']": 0.05, "['b']": 0.1}.get(
            name[name.rfind("["):], 0.0)
        out.append(v + std * n)
    return jax.tree_util.tree_unflatten(tdef, out)


@pytest.mark.parametrize("ell", [1, 2])
def test_sfl_round_matches_the_reference(ell):
    """Two clients, two local steps, experts 4-7 of 8 held, one of them
    favoured by the router: the program's round (client pass, boundary,
    pooled server pass, Adam, FedAvg) gives the reference's step losses,
    first Adam moments, adapters after FedAvg and rows per held expert;
    the reference with a capacity dispatch, which drops the favoured
    expert's overflow, gives other losses and rows."""
    from repro.core.sfl import SflLLM
    from repro.optim import adamw

    cfg = ARCH.replace(experts_held=4, first_expert=4)
    params = _jitter(init_params(cfg, jax.random.key(11)),
                     jax.random.key(12))
    for group in (params["prefix"][0], params["layers"][0]):
        # a latent small enough for the norm's eps to count
        w = group["mixer"]["kv_a_proj"]["w"]
        w = w.at[..., :cfg.kv_lora_rank].multiply(3e-3)
        group["mixer"]["kv_a_proj"]["w"] = w
    router = params["layers"][0]["mlp"]["router"]
    router["w"] = router["w"] * 20.0                    # spread the scores
    router["bias"] = router["bias"].at[:, 5].add(0.5)
    lora = _jitter(init_lora_stack(cfg, jax.random.key(13)),
                   jax.random.key(14))
    ids = np.random.default_rng(ell).integers(0, 512, (2, 2, 1, 33),
                                              np.int32)
    toks, labels = ids[..., :32], ids[..., 1:]
    lr = 1e-2
    sfl = SflLLM(cfg, params, ell_c=ell,
                 train_cfg=TrainConfig(num_clients=2, batch_size=1,
                                       local_steps=2),
                 optimizer=adamw(lr))
    state = sfl.init_state(lora)
    start = jax.device_get((state.lora_client, state.lora_server))
    state, m = sfl.train_round(state, {"tokens": toks, "labels": labels},
                               [1, 1])
    state, m = jax.device_get((state, m))
    ref = reference.sfl_round(_ref_config(cfg), params, *start, toks, labels,
                              ell=ell, lr=lr)

    np.testing.assert_allclose(m["loss"], ref["losses"], rtol=1e-5)
    np.testing.assert_array_equal(m["expert_rows"], ref["rows"])
    cap = math.ceil(1.25 * 32 * cfg.experts_per_token / cfg.num_experts)
    assert ref["rows"][:, :, 1].min() > 2 * cap       # the favoured expert
    # each leaf's relative error: the first Adam moments (the round's
    # gradients) and the adapters' change over the round.  Adam divides
    # the moment by its root mean square, so an element whose moment ends
    # near 0 turns a rounding difference into a large one in its step;
    # the update is compared where every client's moment is above a
    # thousandth of the leaf's largest
    m_ref = jax.tree.leaves((ref["m_client"], ref["m_server"]))
    gaps = {}
    for name, got, want, s0 in (
            ("m", (state.opt_client["m"], state.opt_server["m"]),
             (ref["m_client"], ref["m_server"]), None),
            ("update", (state.lora_client, state.lora_server),
             (ref["lora_client"], ref["lora_server"]), start)):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            if b.size:            # the client of split 1 holds no MoE layer
                if s0 is not None:
                    a, b = (v - jax.tree.leaves(s0)[i] for v in (a, b))
                    mag = np.abs(m_ref[i])
                    if i < len(jax.tree.leaves(ref["m_client"])):
                        mag = np.broadcast_to(mag.min(0), mag.shape)
                    keep = mag > 1e-3 * mag.max()
                    assert keep.mean() > 0.99
                    a, b = a[keep], b[keep]
                gaps[name, i] = float(np.linalg.norm(a - b)
                                      / np.linalg.norm(b))
    assert max(gaps.values()) < 1e-3, gaps

    dropped = reference.sfl_round(_ref_config(cfg), params, *start, toks,
                                  labels, ell=ell, lr=lr, capacity=True)
    assert dropped["rows"].sum() < ref["rows"].sum()
    assert np.max(np.abs(dropped["losses"] - m["loss"])
                  / m["loss"]) > 1e-3
