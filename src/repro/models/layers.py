"""Shared primitive layers: norms, rotary embeddings, MLPs, embeddings.

All layers are pure functions over explicit param pytrees.  Linear layers
route through :func:`dense`, which applies an optional LoRA adapter — the
paper's technique is threaded through every projection this way.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# linear (+ LoRA)
# ---------------------------------------------------------------------------

def _cast_like(x: jax.Array, t: jax.Array) -> jax.Array:
    """Cast ``t`` to x's dtype only when it differs — the guard keeps the
    intent visible in the code and guarantees no convert op is traced for
    already-matching params (callers hoist real mismatches out of the
    depth scan, see ``stack.apply_stack``)."""
    return t if t.dtype == x.dtype else t.astype(x.dtype)


# Backends where ``impl="fused"`` actually routes through the Pallas
# kernels.  Elsewhere (CPU dry runs) the dispatch falls back to the einsum
# composition: the custom-VJP boundary costs ~10% in lost XLA fusion with
# nothing to buy back when no real kernel runs behind it.  Tests extend
# this tuple to force the fused custom-VJP path on CPU.
FUSED_DENSE_BACKENDS = ("tpu",)


def _fused_dense_active() -> bool:
    return jax.default_backend() in FUSED_DENSE_BACKENDS


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
          lora: Optional[dict] = None, lora_scale: float = 1.0,
          impl: str = "einsum",
          adapter_idx: Optional[jax.Array] = None,
          w_scale: Optional[jax.Array] = None) -> jax.Array:
    """y = x @ w (+ b) (+ lora_scale * (x @ a^T) @ b_lora^T).

    WEIGHT-ONLY INT8: with ``w_scale`` (the f32 per-output-channel scale
    from ``repro.precision.quantize_weight_int8``) the base ``w`` is an
    int8 tensor; the fused path hands the (int8, scale) pair straight to
    the q8 kernel, which dequantizes per-tile in VMEM, and the einsum
    paths dequantize up front (the jnp oracle).

    ``lora`` is ``{"a": (r, in), "b": (out, r)}`` or None.  ``impl``
    selects the adapted-projection path: "einsum" runs the base matmul and
    the low-rank pair as separate einsums; "fused" routes through
    ``kernels.lora_matmul`` — one pass over x per projection (custom VJP,
    autotuned tiles) on the backends in ``FUSED_DENSE_BACKENDS``, the
    einsum path elsewhere.

    MULTI-TENANT: with ``adapter_idx`` (a (B,) int32 vector, one entry per
    leading batch row of x) the lora leaves are POOLED —
    ``{"a": (A, r, in), "b": (A, out, r)}`` — and row b of the batch wears
    adapter ``adapter_idx[b]``: "fused" routes through the batched-gather
    ``kernels.lora_matmul.lora_matmul_gathered`` (the per-row gather IS
    the kernel index map), "einsum" takes the equivalent gathered einsum.
    A pool of STATIC size 1 constant-folds back to the single-adapter
    path — bit-identical to passing the unstacked adapter, so the
    single-tenant engine is unchanged by construction.
    """
    if adapter_idx is not None and lora is not None:
        if lora["a"].shape[0] == 1:
            # static size-1 pool: unstack and fall through to the exact
            # single-adapter computation (constant index by construction)
            lora = {"a": lora["a"][0], "b": lora["b"][0]}
            adapter_idx = None
    def _w_dense():
        if w_scale is None:
            return _cast_like(x, w)
        from ..precision import dequantize_weight
        return dequantize_weight(w, w_scale, dtype=x.dtype)

    if adapter_idx is not None and lora is not None:
        if (impl == "fused" and _fused_dense_active()
                and not isinstance(lora_scale, jax.Array)):
            from ..kernels.lora_matmul import lora_matmul_gathered
            # the gather kernel takes a dense base; int8 storage is
            # dequantized at its mouth (still one pass over x)
            y = lora_matmul_gathered(x, _w_dense(), lora["a"], lora["b"],
                                     adapter_idx, scale=float(lora_scale))
        else:
            y = jnp.einsum("...i,io->...o", x, _w_dense())
            a_sel = jnp.take(_cast_like(x, lora["a"]), adapter_idx, axis=0)
            b_sel = jnp.take(_cast_like(x, lora["b"]), adapter_idx, axis=0)
            z = jnp.einsum("b...i,bri->b...r", x, a_sel)
            delta = jnp.einsum("b...r,bor->b...o", z, b_sel)
            y = y + (lora_scale * delta).astype(y.dtype)
    # the fused kernel bakes the scale in as a compile-time constant; a
    # traced scale (per-client alpha/r_k under the hetero-fleet vmap) must
    # take the einsum composition, which multiplies it in-graph
    elif (impl == "fused" and lora is not None and _fused_dense_active()
            and not isinstance(lora_scale, jax.Array)):
        from ..kernels.lora_matmul import lora_matmul
        y = lora_matmul(x, w, lora["a"], lora["b"], scale=float(lora_scale),
                        w_scale=w_scale)
    else:
        y = jnp.einsum("...i,io->...o", x, _w_dense())
        if lora is not None:
            z = jnp.einsum("...i,ri->...r", x, _cast_like(x, lora["a"]))
            delta = jnp.einsum("...r,or->...o", z, _cast_like(x, lora["b"]))
            y = y + (lora_scale * delta).astype(y.dtype)
    if b is not None:
        y = y + _cast_like(y, b)
    return y


def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: float | None = None) -> dict:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_lora(key, d_in: int, d_out: int, rank: int, dtype) -> dict:
    """LoRA init per Hu et al.: A ~ N(0, 1/r), B = 0 (so delta starts at 0)."""
    ka, _ = jax.random.split(key)
    return {
        "a": (jax.random.normal(ka, (rank, d_in), jnp.float32) * rank ** -0.5).astype(dtype),
        "b": jnp.zeros((d_out, rank), dtype),
    }


# ---------------------------------------------------------------------------
# norms (computed in f32, cast back)
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x: jax.Array, p: dict) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps)
    return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)


def init_norm(cfg, d: int, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).

    Angles are computed in f32 (positions up to 512k), but the rotation
    itself runs in x's dtype: keeping bf16 values bf16 end-to-end stops
    XLA from hoisting a full-width f32 twin of the KV cache through the
    decode loop (EXPERIMENTS.md §Perf #8)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                   # (D/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(angles)[..., :, None, :].astype(x.dtype)         # (...,S,1,D/2)
    sin = jnp.sin(angles)[..., :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_mlp(cfg, x: jax.Array, p: dict, lora: Optional[dict] = None,
               lora_scale: float = 1.0, dense_impl: str = "einsum",
               adapter_idx: Optional[jax.Array] = None) -> jax.Array:
    def _l(name):
        return None if lora is None or name not in lora else lora[name]

    g = dense(x, p["w_gate"]["w"], lora=_l("gate"), lora_scale=lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["w_gate"].get("w_scale"))
    u = dense(x, p["w_up"]["w"], lora=_l("up"), lora_scale=lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["w_up"].get("w_scale"))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return dense(h, p["w_down"]["w"], lora=_l("down"), lora_scale=lora_scale,
                 impl=dense_impl, adapter_idx=adapter_idx,
                 w_scale=p["w_down"].get("w_scale"))


_GELU_C = math.sqrt(2.0 / math.pi)


@jax.custom_vjp
def gelu_new(h: jax.Array) -> jax.Array:
    """GPT-2's tanh GELU, computed in f32 and returned in h's dtype.  The
    backward keeps only ``h`` and recomputes the tanh from it: plain
    autodiff would save four more f32 arrays of h's shape."""
    return jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(h.dtype)


def _gelu_new_fwd(h):
    return gelu_new(h), h


def _gelu_new_bwd(h, g):
    x = h.astype(jnp.float32)
    t = jnp.tanh(_GELU_C * (x + 0.044715 * x ** 3))
    dx = 0.5 * (1 + t) + 0.5 * x * (1 - t * t) * _GELU_C * (
        1 + 3 * 0.044715 * x * x)
    return ((g.astype(jnp.float32) * dx).astype(h.dtype),)


gelu_new.defvjp(_gelu_new_fwd, _gelu_new_bwd)


def gelu_mlp(cfg, x: jax.Array, p: dict, lora: Optional[dict] = None,
             lora_scale: float = 1.0, dense_impl: str = "einsum",
             adapter_idx: Optional[jax.Array] = None) -> jax.Array:
    def _l(name):
        return None if lora is None or name not in lora else lora[name]

    h = dense(x, p["w_up"]["w"], p["w_up"].get("b"), lora=_l("up"),
              lora_scale=lora_scale, impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["w_up"].get("w_scale"))
    h = gelu_new(h)
    return dense(h, p["w_down"]["w"], p["w_down"].get("b"), lora=_l("down"),
                 lora_scale=lora_scale, impl=dense_impl,
                 adapter_idx=adapter_idx,
                 w_scale=p["w_down"].get("w_scale"))


def apply_mlp(cfg, x: jax.Array, p: dict, lora: Optional[dict] = None,
              lora_scale: float = 1.0, dense_impl: str = "einsum",
              adapter_idx: Optional[jax.Array] = None) -> jax.Array:
    if cfg.mlp_kind == "swiglu":
        return swiglu_mlp(cfg, x, p, lora, lora_scale, dense_impl, adapter_idx)
    return gelu_mlp(cfg, x, p, lora, lora_scale, dense_impl, adapter_idx)


def init_mlp(cfg, key, dtype) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    bias = cfg.norm == "layernorm"          # GPT-2 family carries biases
    if cfg.mlp_kind == "swiglu":
        return {
            "w_gate": init_dense(ks[0], d, ff, dtype),
            "w_up": init_dense(ks[1], d, ff, dtype),
            "w_down": init_dense(ks[2], ff, d, dtype),
        }
    return {
        "w_up": init_dense(ks[0], d, ff, dtype, bias=bias),
        "w_down": init_dense(ks[1], ff, d, dtype, bias=bias),
    }


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embeddings(cfg, key, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
                 * 0.02).astype(dtype)}
    if cfg.pos_emb == "learned":
        p["pos"] = (jax.random.normal(ks[1], (cfg.max_seq_len, cfg.d_model), jnp.float32)
                    * 0.02).astype(dtype)
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(ks[2], (cfg.d_model, cfg.vocab_size), jnp.float32)
                        * cfg.d_model ** -0.5).astype(dtype)
    return p


def embed(cfg, p: dict, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.pos_emb == "learned":
        pos_table = p["pos"]
        idx = jnp.clip(positions, 0, pos_table.shape[0] - 1)
        x = x + jnp.take(pos_table, idx, axis=0)
    return x


def unembed(cfg, p: dict, x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    return jnp.einsum("...d,dv->...v", x, _cast_like(x, w))
