"""Mixture-of-Experts FFN — GShard-style capacity-based dispatch.

Tokens are reshaped into small groups (``group_size`` tokens per group) so
the (G, S_g, E, C) dispatch/combine tensors stay bounded:

    elements = tokens * S_g * k * capacity_factor

With the default 128-token groups this is ~1.3e9 elements for the
prefill_32k x olmoe shape — shardable over the ("data","model") mesh, with
the group axis on "data" and the expert axis on "model" (expert parallelism;
GSPMD materializes the token redistribution as all-to-all-like collectives).

The einsum formulation is deliberate: it is what GSPMD shards without
bespoke collectives.  A sort/ragged-dot implementation is a recorded perf
lever (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import init_dense, swiglu_mlp, init_mlp


def init_moe(cfg, key, dtype) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": init_dense(ks[0], d, e, dtype, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, ff, d), jnp.float32) * ff ** -0.5).astype(dtype),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(cfg, ks[4], dtype)
    return p


def _pick_group_size(seq: int, target: int) -> int:
    """Largest divisor of ``seq`` that is <= target."""
    g = min(seq, target)
    while seq % g:
        g -= 1
    return g


def apply_moe(cfg, p: dict, x: jax.Array, *, group_size: int = 128,
              capacity_factor: float = 1.25,
              shard_specs=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,d), aux load-balance loss scalar).

    ``shard_specs = (dp_axes, tp_axis)`` pins the dispatch pipeline:
    groups over dp, experts over tp — forcing the token redistribution into
    one all-to-all-shaped exchange instead of per-expert partial-sum
    all-reduces (EXPERIMENTS.md §Perf, llama4 hillclimb)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    sg = _pick_group_size(S, group_size)
    G = B * (S // sg)
    xg = x.reshape(G, sg, d)

    if shard_specs is not None:
        from jax.sharding import PartitionSpec as P
        dp, tp = shard_specs
        _c = jax.lax.with_sharding_constraint
    else:
        _c = P = dp = tp = None

    # --- routing (f32) ----------------------------------------------------
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    if shard_specs is not None:
        # top_k over a tp-sharded expert dim lowers to a distributed sort
        # (thousands of small all-reduces); route replicated-per-dp-shard
        logits = _c(logits, P(dp, None, None))
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,sg,E)
    gates, ids = jax.lax.top_k(probs, K)                         # (G,sg,K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- capacity positions -------------------------------------------------
    # flatten the (token, choice) axis; earlier tokens / higher choices win
    ids_f = ids.reshape(G, sg * K)
    onehot = jax.nn.one_hot(ids_f, E, dtype=jnp.int32)           # (G,sg*K,E)
    pos = jnp.cumsum(onehot, axis=1) - 1                         # 0-based slot
    pos_f = jnp.sum(pos * onehot, axis=-1)                       # (G,sg*K)
    cap = max(1, int(math.ceil(sg * K / E * capacity_factor)))
    cap = -(-cap // 4) * 4 if cap > 4 else cap                   # pad to x4
    keep = pos_f < cap

    # --- combine / dispatch tensors  (G, sg, E, C) --------------------------
    ids_k = ids_f.reshape(G, sg, K)
    pos_k = pos_f.reshape(G, sg, K)
    keep_k = keep.reshape(G, sg, K)
    combine = jnp.zeros((G, sg, E, cap), jnp.float32)
    for j in range(K):
        oh = (jax.nn.one_hot(ids_k[:, :, j], E, dtype=jnp.float32)[..., None]
              * jax.nn.one_hot(pos_k[:, :, j], cap, dtype=jnp.float32)[..., None, :])
        combine = combine + oh * (gates[:, :, j] * keep_k[:, :, j])[..., None, None]
    dispatch = (combine > 0).astype(x.dtype)

    # --- expert computation (E sharded over "model": expert parallelism) ---
    if shard_specs is not None:
        combine = _c(combine, P(dp, None, tp, None))
        dispatch = _c(dispatch, P(dp, None, tp, None))
    xd = jnp.einsum("gsd,gsec->gecd", xg, dispatch)              # (G,E,C,d)
    if shard_specs is not None:
        # tokens now live on their expert's shard; d replicated per shard so
        # the expert matmuls contract locally (weights FSDP-gathered once)
        xd = _c(xd, P(dp, tp, None, None))
    h_g = jnp.einsum("gecd,edf->gecf", xd, p["w_gate"].astype(x.dtype))
    h_u = jnp.einsum("gecd,edf->gecf", xd, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(h_g.astype(jnp.float32)).astype(x.dtype) * h_u
    if shard_specs is not None:
        h = _c(h, P(dp, tp, None, None))
    yd = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    if shard_specs is not None:
        yd = _c(yd, P(dp, tp, None, None))
    y = jnp.einsum("gecd,gsec->gsd", yd, combine.astype(x.dtype))

    # --- aux load-balance loss (Switch-style) -------------------------------
    density = probs.mean(axis=(0, 1))                            # (E,)
    top1 = jax.nn.one_hot(ids[..., 0], E, dtype=jnp.float32)
    density_proxy = top1.mean(axis=(0, 1))
    aux = E * jnp.sum(density * density_proxy)

    out = y.reshape(B, S, d)
    if cfg.shared_expert:
        out = out + swiglu_mlp(cfg, x, p["shared"])
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# dropless routing over the experts this chip holds
# ---------------------------------------------------------------------------

def init_moe_dropless(cfg, key, dtype) -> dict:
    """Router over all ``num_experts`` and its selection bias, the held
    experts' SwiGLU weights, and the shared expert."""
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.held_experts
    ks = jax.random.split(key, 6)
    nrm = lambda k, shp, fan: (jax.random.normal(k, shp, jnp.float32)
                               * fan ** -0.5).astype(dtype)
    p = {"router": init_dense(ks[0], d, cfg.num_experts, dtype, scale=0.02),
         "w_gate": nrm(ks[1], (e, d, f), d), "w_up": nrm(ks[2], (e, d, f), d),
         "w_down": nrm(ks[3], (e, f, d), f)}
    p["router"]["bias"] = jnp.zeros((cfg.num_experts,), dtype)
    if cfg.shared_expert:
        p["shared"] = init_mlp(cfg.replace(d_ff=cfg.shared_d_ff or cfg.d_ff),
                               ks[4], dtype)
    return p


def route(cfg, p: dict, x2: jax.Array):
    """(ids (T, k) of the chosen experts over all of them, gate weights
    (T, k) f32), as DeepSeek-V3's gate (``noaux_tc`` with one group)
    computes them: scores are the sigmoid of the router logits, in float32
    at full precision; the correction bias moves the choice only; the
    chosen scores, normalised to sum 1, times routed_scale, are the
    weights."""
    logits = jnp.dot(x2.astype(jnp.float32),
                     p["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choose = scores + p["router"]["bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(choose, cfg.experts_per_token)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg.routed_scale


def apply_moe_dropless(cfg, p: dict, x: jax.Array):
    """Returns (output (B, S, d), rows (held,) int32: the (token, choice)
    rows routed to each held expert).

    Routes every token over all ``num_experts``; each (token, choice) row
    that picks a held expert reaches it (no capacity, nothing dropped),
    rows sorted by expert through ``kernels.grouped_matmul``; the output
    is the held experts' part of the mixture plus the shared expert.
    Phases: ``moe.route`` (gate, top-k, sort and gather), ``moe.experts``
    (the grouped matmuls, forward and dX), ``moe.combine`` (un-permute and
    weighted sum), ``moe.shared``."""
    from ..kernels.grouped_matmul import expert_swiglu

    B, S, d = x.shape
    E, k = cfg.held_experts, cfg.experts_per_token
    x2 = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        ids, w = route(cfg, p, x2)
        local = (ids - cfg.first_expert).reshape(-1)
        held = (local >= 0) & (local < E)
        expert = jnp.where(held, local, E)                # E: not held here
        order = jnp.argsort(expert, stable=True)
        group_sizes = jnp.bincount(expert, length=E + 1).astype(jnp.int32)
        xs = jnp.take(x2, order // k, axis=0)
        gate = jnp.take(jnp.where(held, w.reshape(-1), 0.0), order)
    with jax.named_scope("moe.experts"):
        ys = expert_swiglu(xs, gate, group_sizes, p["w_gate"], p["w_up"],
                           p["w_down"])
    with jax.named_scope("moe.combine"):
        out = jnp.sum(jnp.take(ys, jnp.argsort(order), axis=0)
                      .reshape(B * S, k, d), axis=1)
    out = out.astype(x.dtype).reshape(B, S, d)
    if cfg.shared_expert:
        # the backward keeps the gate and up projections (the matmul
        # outputs) alone and makes the activation again from them: at the
        # Moonlight cut's SFL round (K=5 x S=2048 on the server) autodiff's
        # own residuals take 1.73 GB more and no longer fit a v5e
        shared = jax.checkpoint(
            lambda h: swiglu_mlp(cfg, h, p["shared"]),
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        with jax.named_scope("moe.shared"):
            out = out + shared(x)
    return out, group_sizes[:E]
