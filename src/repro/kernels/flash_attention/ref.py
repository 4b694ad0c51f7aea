"""Pure-jnp oracle: full-score-matrix causal (optionally windowed) GQA
attention, layout (B, H, S, D)."""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def flash_decode_ref(q, k, v, lengths, *, window: int = 0):
    """Decode oracle: q (B, KH, G, D) — one query token per slot, GQA
    folded; k/v (B, KH, L, D); lengths (B,) live entries per slot (cache
    entries laid out contiguously at [0, length)).  Masked full-score
    softmax in f32 — the jnp twin of ``decode.flash_decode_kernel`` and
    the off-TPU fallback path of ``ops.flash_decode``."""
    B, KH, G, D = q.shape
    L = k.shape[2]
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D ** -0.5
    k_idx = jnp.arange(L)
    mask = k_idx[None, :] < lengths[:, None]                 # (B, L)
    if window:
        mask &= k_idx[None, :] > lengths[:, None] - 1 - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True)) * mask[:, None, None]
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def flash_decode_q8_ref(q, k, v, k_scale, v_scale, lengths, *,
                        window: int = 0):
    """Int8-KV decode oracle: dequantizes exactly like the q8 kernel
    (int8 -> f32 * per-KV-head scale) then runs ``flash_decode_ref``.
    k/v: int8 (B, KH, L, D); k_scale/v_scale: f32 (KH,)."""
    kf = k.astype(jnp.float32) * k_scale[None, :, None, None]
    vf = v.astype(jnp.float32) * v_scale[None, :, None, None]
    return flash_decode_ref(q, kf, vf, lengths, window=window)


def paged_decode_q8_ref(q, k_pages, v_pages, k_scale, v_scale, lengths,
                        block_tables):
    """Int8-KV paged decode oracle: dequantize the pool per KV head, then
    gather and score with ``paged_decode_ref``."""
    kf = k_pages.astype(jnp.float32) * k_scale[:, None, None, None]
    vf = v_pages.astype(jnp.float32) * v_scale[:, None, None, None]
    return paged_decode_ref(q, kf, vf, lengths, block_tables)


def paged_decode_ref(q, k_pages, v_pages, lengths, block_tables):
    """Paged decode oracle: q (B, KH, G, D) — one query token per slot,
    GQA folded; k_pages/v_pages (KH, NP, PS, D) — the GLOBAL page pool
    shared by every slot (page 0 is the never-allocated null page);
    block_tables (B, MP) int32 — entry j of a slot's row names the page
    holding its absolute positions [j*PS, (j+1)*PS); lengths (B,) live
    entries per slot.

    Gathers each slot's pages into its logical (MP*PS,) KV view — entry i
    of the gathered axis IS absolute position i, so the length mask of
    ``flash_decode_ref`` applies unchanged.  The jnp twin of
    ``paged_decode.paged_decode_kernel`` and the off-TPU fallback of
    ``ops.paged_decode``."""
    B = q.shape[0]
    KH, _, PS, D = k_pages.shape
    MP = block_tables.shape[1]
    kg = k_pages[:, block_tables]                # (KH, B, MP, PS, D)
    vg = v_pages[:, block_tables]
    k = kg.transpose(1, 0, 2, 3, 4).reshape(B, KH, MP * PS, D)
    v = vg.transpose(1, 0, 2, 3, 4).reshape(B, KH, MP * PS, D)
    return flash_decode_ref(q, k, v, lengths)


def flash_attention_ref(q, k, v, *, window: int = 0, seq_k: int = 0):
    """q: (B, H, Sq, D); k/v: (B, KH, Sk, D); causal with q and k aligned at
    the sequence end (q_pos = Sk - Sq + arange(Sq)).  seq_k masks padding
    beyond the true Sk (0 = no padding)."""
    B, H, Sq, D = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    G = H // KH
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * D ** -0.5
    q_pos = (Sk - Sq) + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    if seq_k:
        mask &= k_pos[None, :] < seq_k
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


def _causal_mask(Sq: int, Sk: int, window: int):
    q_pos = (Sk - Sq) + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask


def _scaled_q(q, mxu_dtype):
    """q times D^-1/2, rounded to the operand dtype after the scaling."""
    return (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(mxu_dtype)


def _scores(q, k, mxu_dtype, window):
    """(B, H, Sq, Sk) f32 scores from mxu-dtype operands, masked; and the
    mask.  GQA by repeating KV heads."""
    H, Sq = q.shape[1], q.shape[2]
    KH, Sk = k.shape[1], k.shape[2]
    kr = jnp.repeat(k, H // KH, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", _scaled_q(q, mxu_dtype),
                   kr.astype(mxu_dtype), preferred_element_type=jnp.float32)
    mask = _causal_mask(Sq, Sk, window)[None, None]
    return jnp.where(mask, s, NEG_INF), mask


def flash_attention_train_ref(q, k, v, *, window: int = 0, mxu_dtype=None):
    """(o, lse) under the training kernels' contract: q (B, H, Sq, D), k
    (B, KH, Sk, D), v (B, KH, Sk, Dv), causal with q aligned at the end of
    k; the score and PV matmuls take ``mxu_dtype`` operands (default: the
    input dtype; q scaled by D^-1/2 before its cast) with f32
    accumulation, softmax statistics in f32.  lse: f32 (B, H, Sq)."""
    mxu_dtype = mxu_dtype or q.dtype
    H, KH = q.shape[1], k.shape[1]
    s, mask = _scores(q, k, mxu_dtype, window)
    m = s.max(-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    vr = jnp.repeat(v, H // KH, axis=1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(mxu_dtype),
                   vr.astype(mxu_dtype),
                   preferred_element_type=jnp.float32) / l
    return o.astype(q.dtype), (m + jnp.log(l))[..., 0]


def flash_attention_train_bwd_ref(q, k, v, o, lse, do, *, window: int = 0,
                                  mxu_dtype=None):
    """(dq, dk, dv) under the same contract, p recomputed from lse as the
    backward kernels do; KV gradients summed over each head group."""
    mxu_dtype = mxu_dtype or q.dtype
    B, H, Sq, D = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    G = H // KH
    s, mask = _scores(q, k, mxu_dtype, window)
    p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
    f32 = dict(preferred_element_type=jnp.float32)
    c = lambda x: x.astype(mxu_dtype)
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", c(p), c(do), **f32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", c(do), c(vr), **f32)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", c(ds), c(kr), **f32) * D ** -0.5
    dk = jnp.einsum("bhqk,bhqd->bhkd", c(ds), _scaled_q(q, mxu_dtype), **f32)
    group = lambda t: t.reshape(B, KH, G, Sk, t.shape[-1]).sum(2)
    return (dq.astype(q.dtype), group(dk).astype(k.dtype),
            group(dv).astype(v.dtype))
