"""Top-level model: init / abstract init / forward / loss / prefill / decode.

Params pytree:
    {"embed": {...}, "layers": (per-pattern-position stacked blocks, ...),
     "final_norm": {...}}

LoRA pytree mirrors "layers" only (the trainable set — the paper's
technique).  ``init_lora`` builds adapters for ``cfg.lora_targets``.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import stack as stack_mod
from .layers import embed, init_embeddings, init_lora, init_norm, unembed, apply_norm
from .stack import Runtime

IGNORE_ID = -1

_ATTN_TARGETS = {"q": ("wq",), "k": ("wk",), "v": ("wv",), "o": ("wo",)}
_MLP_TARGETS = {"gate": "w_gate", "up": "w_up", "down": "w_down"}
_SSM_TARGETS = {"ssm_in": "in_proj", "ssm_out": "out_proj"}
_MLA_TARGETS = ("q_proj", "kv_b_proj")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> dict:
    """A config with leading layers (``cfg.prefix``) adds "prefix": their
    blocks, as ``stack.init_stack(pattern=cfg.prefix)`` lays them out."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "embed": init_embeddings(cfg, k1, dtype),
        "layers": stack_mod.init_stack(cfg, k2, dtype),
        "final_norm": init_norm(cfg, cfg.d_model, dtype),
    }
    if cfg.prefix:
        p["prefix"] = stack_mod.init_stack(cfg, k3, dtype, pattern=cfg.prefix)
    return p


def abstract_params(cfg: ArchConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0), dtype))


def _lora_dims(cfg: ArchConfig, pat, target: str) -> Optional[Tuple[str, int, int]]:
    """-> (block_key, d_in, d_out) for a target name, or None if absent."""
    h, kh, hd, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    if target in _MLA_TARGETS and pat.mixer == "mla":
        if target == "q_proj":
            return ("mixer", d, h * cfg.qk_head_dim)
        return ("mixer", cfg.kv_lora_rank,
                h * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    if target in _ATTN_TARGETS and pat.mixer == "attention":
        if target == "q":
            return ("mixer", d, h * hd)
        if target == "k":
            return ("mixer", d, kh * hd)
        if target == "v":
            return ("mixer", d, kh * hd)
        return ("mixer", h * hd, d)
    if target in _SSM_TARGETS and pat.mixer == "mamba":
        d_in = cfg.d_inner
        total = 2 * d_in + 2 * cfg.ssm_state + cfg.ssm_num_heads
        if target == "ssm_in":
            return ("mixer", d, total)
        return ("mixer", d_in, d)
    if target in _MLP_TARGETS and pat.mlp == "dense":
        ff = cfg.d_ff
        if target == "down":
            return ("mlp", ff, d)
        return ("mlp", d, ff)
    return None


def init_lora_stack(cfg: ArchConfig, key, rank: Optional[int] = None,
                    dtype=jnp.float32) -> Tuple[Any, ...]:
    """LoRA adapters, stacked over repeats, tuple over pattern positions.
    With leading layers (``cfg.prefix``): {"prefix": theirs, laid out the
    same way, "layers": the repeating ones'}."""
    if cfg.prefix:
        kp, kl = jax.random.split(key)
        return {"prefix": _lora_stack(cfg, cfg.prefix, 1, kp, rank, dtype),
                "layers": _lora_stack(cfg, cfg.pattern, cfg.pattern_repeats,
                                      kl, rank, dtype)}
    return _lora_stack(cfg, cfg.pattern, cfg.pattern_repeats, key, rank,
                       dtype)


def _lora_stack(cfg, pattern, R, key, rank, dtype):
    rank = rank or cfg.lora_rank
    P = len(pattern)
    keys = jax.random.split(key, P * R).reshape(P, R)
    out = []
    for pi, pat in enumerate(pattern):
        per_rep = []
        for ri in range(R):
            kk = jax.random.split(keys[pi, ri], max(len(cfg.lora_targets), 1))
            block: dict = {}
            for ti, t in enumerate(cfg.lora_targets):
                dims = _lora_dims(cfg, pat, t)
                if dims is None:
                    continue
                where, d_in, d_out = dims
                block.setdefault(where, {})[t] = init_lora(kk[ti], d_in, d_out,
                                                           rank, dtype)
            per_rep.append(block)
        if per_rep[0]:
            out.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
        else:
            out.append({})
    return tuple(out)


def abstract_lora(cfg: ArchConfig, rank: Optional[int] = None, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: init_lora_stack(cfg, jax.random.key(0), rank, dtype))


def lora_num_params(cfg: ArchConfig, rank: Optional[int] = None) -> int:
    import math

    tree = abstract_lora(cfg, rank)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, tokens, frontend_emb, positions):
    x = embed(cfg, params["embed"], tokens, positions[-tokens.shape[1]:]
              if frontend_emb is not None else positions)
    if frontend_emb is not None:
        x = jnp.concatenate([frontend_emb.astype(x.dtype), x], axis=1)
    return x


def forward(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            lora=None, rt: Runtime = Runtime(), frontend_emb=None,
            mode: str = "train"):
    """Full-sequence forward.  tokens: (B, S_text); frontend_emb: (B, F, d).

    Returns (logits (B, S, V), aux_loss).  S = F + S_text.
    """
    B = tokens.shape[0]
    S = tokens.shape[1] + (frontend_emb.shape[1] if frontend_emb is not None else 0)
    positions = jnp.arange(S, dtype=jnp.int32)
    x = _embed_inputs(cfg, params, tokens, frontend_emb, positions)
    if cfg.prefix:
        x, _, _ = stack_mod.apply_stack(
            cfg, params["prefix"], x, positions=positions,
            lora=None if lora is None else lora["prefix"], rt=rt,
            mode=mode, pattern=cfg.prefix)
        lora = None if lora is None else lora["layers"]
    x, _, aux = stack_mod.apply_stack(cfg, params["layers"], x,
                                      positions=positions, lora=lora, rt=rt,
                                      mode="train" if mode == "train" else mode)
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params["embed"], x)
    if rt.dp_axes:
        from jax.sharding import PartitionSpec
        logits = jax.lax.with_sharding_constraint(
            logits, PartitionSpec(rt.dp_axes, None, rt.tp_axis))
    return logits, aux


def loss_fn(cfg: ArchConfig, params: dict, lora, batch: dict, *,
            rt: Runtime = Runtime()) -> Tuple[jax.Array, dict]:
    """Causal-LM cross entropy.  batch: tokens (B,S), labels (B,S) with
    IGNORE_ID masking, optional frontend_emb."""
    logits, aux = forward(cfg, params, batch["tokens"], lora=lora, rt=rt,
                          frontend_emb=batch.get("frontend_emb"))
    labels = batch["labels"]
    F = logits.shape[1] - labels.shape[1]
    if F > 0:
        logits = logits[:, F:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels != IGNORE_ID).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}


def prefill(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            lora=None, rt: Runtime = Runtime(), frontend_emb=None,
            cache_len: int = 0, logit_index=None):
    """Build decode caches; returns (last-token logits (B, V), caches).

    ``logit_index`` (dynamic scalar, TEXT-relative) reads the logits at
    that token index instead of the final one — bucket-padded serving
    prompts put the true last prompt token before the padding tail.  With
    ``frontend_emb`` the frontend prefix offset is added internally."""
    B = tokens.shape[0]
    S = tokens.shape[1] + (frontend_emb.shape[1] if frontend_emb is not None else 0)
    positions = jnp.arange(S, dtype=jnp.int32)
    x = _embed_inputs(cfg, params, tokens, frontend_emb, positions)
    x, caches, _ = stack_mod.apply_stack(cfg, params["layers"], x,
                                         positions=positions, lora=lora, rt=rt,
                                         mode="prefill", cache_len=cache_len)
    if logit_index is None:
        x = x[:, -1:]
    else:
        F = frontend_emb.shape[1] if frontend_emb is not None else 0
        x = jax.lax.dynamic_slice_in_dim(x, logit_index + F, 1, axis=1)
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params["embed"], x)[:, 0]
    return logits, caches


def decode_step(cfg: ArchConfig, params: dict, token: jax.Array, caches,
                cur_index, *, lora=None, rt: Runtime = Runtime(),
                adapter_idx=None):
    """One decode step.  token: (B, 1) int32; cur_index: scalar int32, or
    a per-sequence (B,) vector when each sequence sits at its own absolute
    position (continuous-batching slots).

    ``adapter_idx`` (B,): multi-tenant decode — lora leaves are (R, A, ...)
    pools and slot b wears adapter ``adapter_idx[b]``.

    Returns (logits (B, V), new caches)."""
    B = token.shape[0]
    cur_index = jnp.asarray(cur_index, jnp.int32)
    positions = (cur_index[:, None] if cur_index.ndim
                 else jnp.full((1,), cur_index, jnp.int32))
    x = embed(cfg, params["embed"], token, positions)
    x, caches, _ = stack_mod.apply_stack(cfg, params["layers"], x,
                                         positions=positions, lora=lora, rt=rt,
                                         mode="decode", caches=caches,
                                         cur_index=cur_index,
                                         adapter_idx=adapter_idx)
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params["embed"], x)[:, 0]
    return logits, caches


def paged_decode_step(cfg: ArchConfig, params: dict, token: jax.Array, caches,
                      block_tables, cur_index, *, lora=None,
                      rt: Runtime = Runtime(), adapter_idx=None):
    """One decode step over the paged KV pool.  token: (B, 1) int32;
    block_tables: (B, MP) int32 page ids; cur_index: (B,) absolute
    positions (serving slots each at their own).

    ``adapter_idx`` (B,): multi-tenant decode — lora leaves are (R, A, ...)
    pools and slot b wears adapter ``adapter_idx[b]`` (the batched-gather
    LoRA kernel under ``rt.dense_impl == "fused"``).

    Returns (logits (B, V), new caches) — the caches are the page pools
    from ``init_paged_cache``, updated in place (donation-friendly)."""
    cur_index = jnp.asarray(cur_index, jnp.int32)
    positions = cur_index[:, None]
    x = embed(cfg, params["embed"], token, positions)
    x, caches, _ = stack_mod.apply_stack(cfg, params["layers"], x,
                                         positions=positions, lora=lora, rt=rt,
                                         mode="decode", caches=caches,
                                         cur_index=cur_index,
                                         block_tables=block_tables,
                                         adapter_idx=adapter_idx)
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params["embed"], x)[:, 0]
    return logits, caches


def paged_prefill_chunk(cfg: ArchConfig, params: dict, tokens: jax.Array,
                        caches, block_table, start, logit_index, *,
                        lora=None, rt: Runtime = Runtime()):
    """One chunked-prefill step: tokens (1, C) with C == page_size, the
    prompt chunk covering absolute positions [start, start + C);
    block_table (MP,) the slot's page row (the chunk's page already
    allocated); logit_index the CHUNK-relative index to read logits at
    (clamped by the caller; only meaningful on the final chunk).

    Returns (logits (1, V), new caches).  One compiled executable serves
    every chunk of every prompt — start/logit_index are traced scalars."""
    C = tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(C, dtype=jnp.int32)
    x = embed(cfg, params["embed"], tokens, positions)
    x, caches, _ = stack_mod.apply_stack(cfg, params["layers"], x,
                                         positions=positions, lora=lora, rt=rt,
                                         mode="chunk", caches=caches,
                                         cur_index=start,
                                         block_tables=block_table)
    x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params["embed"], x)[:, 0]
    return logits, caches


def init_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    return stack_mod.init_stack_cache(cfg, batch, cache_len, dtype)


def init_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    return stack_mod.init_paged_stack_cache(cfg, num_pages, page_size, dtype)


def abstract_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, cache_len, dtype))


def num_params(cfg: ArchConfig) -> int:
    import math

    tree = abstract_params(cfg)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(tree))


def num_active_params(cfg: ArchConfig) -> int:
    """Active params per token (MoE: only routed experts count)."""
    total = num_params(cfg)
    if not cfg.num_experts:
        return total
    # subtract inactive expert weights
    per_expert = 3 * cfg.d_model * cfg.d_ff
    n_moe_layers = sum(1 for p in cfg.layer_kinds if p.mlp == "moe")
    inactive = (cfg.num_experts - cfg.experts_per_token) * per_expert * n_moe_layers
    return total - inactive
