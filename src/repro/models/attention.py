"""GQA attention: naive, chunked online-softmax (flash-style, pure JAX),
sliding-window variants, and a KV-cache decode path.

The chunked implementation is the mathematical twin of
``repro.kernels.flash_attention`` — the Pallas kernel targets TPU VMEM
tiling, this one is what dry-runs lower (the CPU host target cannot compile
Pallas).  Both share the same online-softmax recurrence.

KV caches store *post-rope* keys plus an absolute-position array so that
sliding-window ring buffers stay correct at arbitrary offsets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense, init_dense, rmsnorm

NEG_INF = -1e30


def init_attention(cfg, key, dtype) -> dict:
    d = cfg.d_model
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    bias = cfg.norm == "layernorm"
    return {
        "wq": init_dense(ks[0], d, h * hd, dtype, bias=bias),
        "wk": init_dense(ks[1], d, kh * hd, dtype, bias=bias),
        "wv": init_dense(ks[2], d, kh * hd, dtype, bias=bias),
        "wo": init_dense(ks[3], h * hd, d, dtype, bias=bias),
    }


def _proj_qkv(cfg, p, x, lora, lora_scale, dense_impl="einsum",
              adapter_idx=None):
    """Project and reshape to (B, S, H|KH, D), rope NOT yet applied."""
    B, S, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def _l(name):
        return None if lora is None or name not in lora else lora[name]

    q = dense(x, p["wq"]["w"], p["wq"].get("b"), _l("q"), lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["wq"].get("w_scale"))
    k = dense(x, p["wk"]["w"], p["wk"].get("b"), _l("k"), lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["wk"].get("w_scale"))
    v = dense(x, p["wv"]["w"], p["wv"].get("b"), _l("v"), lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["wv"].get("w_scale"))
    return (q.reshape(B, S, h, hd), k.reshape(B, S, kh, hd), v.reshape(B, S, kh, hd))


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, window: int):
    """(Sq, Sk) bool; k_pos < 0 marks padding slots."""
    m = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def naive_attention(q, k, v, q_pos, k_pos, window: int = 0) -> jax.Array:
    """Full-score-matrix attention (small shapes / oracle / decode).

    Operands stay in their input dtype with f32 MXU accumulation
    (preferred_element_type) — for bf16 KV caches this avoids materializing
    an f32 copy of the whole cache (decode_32k: 3x cache traffic saved,
    EXPERIMENTS.md §Perf #8); for f32 inputs it is bit-identical to the
    cast formulation."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qr = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(_mask(q_pos, k_pos, window)[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _chunk_kv(k, v, k_pos, kv_chunk):
    B, Sk, KH, D = k.shape
    pad = (-Sk) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    n = (Sk + pad) // kv_chunk
    kc = k.reshape(B, n, kv_chunk, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, kv_chunk, KH, -1).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n, kv_chunk)
    return kc, vc, pc, pad


def _flash_fwd_scan(q, k, v, q_pos, k_pos, window, kv_chunk,
                    s_low_precision: bool = False):
    """Online-softmax forward.  Returns (out (B,Sq,KH,G,D) f32,
    lse (B,KH,G,Sq) f32).

    ``s_low_precision`` keeps the score einsum in the input dtype (bf16
    accumulation): when the TP degree does not divide the KV-head count the
    head_dim contraction gets sharded and the score tiles are all-reduced —
    bf16 halves that wire traffic (llama4 hillclimb, EXPERIMENTS.md §Perf).
    """
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    kc, vc, pc, _ = _chunk_kv(k, v, k_pos, kv_chunk)
    qs = (q if s_low_precision else q.astype(jnp.float32))
    qs = qs.reshape(B, Sq, KH, G, D) * jnp.asarray(D ** -0.5, qs.dtype)
    qf = qs.astype(jnp.float32) if not s_low_precision else qs

    m0 = jnp.full((B, KH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, KH, G, v.shape[-1]), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        ki, vi, pi = xs
        if s_low_precision:
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qs, ki).astype(jnp.float32)
        else:
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, ki.astype(jnp.float32))
        valid = _mask(q_pos, pi, window)                       # (Sq, C)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * valid[None, None, None]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        # p rides to the MXU in bf16 (flash-kernel convention): halves the
        # probability-tile HBM traffic of this jnp twin; acc stays f32.
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vi.dtype), vi,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    denom = jnp.maximum(l, 1e-30)
    out = acc / denom.transpose(0, 3, 1, 2)[..., None]
    lse = m + jnp.log(denom)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_attention(q, k, v, q_pos, k_pos, window: int, kv_chunk: int,
                     s_low_precision: bool = False):
    out, _ = _flash_fwd_scan(q, k, v, q_pos, k_pos, window, kv_chunk,
                             s_low_precision)
    B, Sq, H, _ = q.shape
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _flash_fwd(q, k, v, q_pos, k_pos, window, kv_chunk,
               s_low_precision=False):
    out, lse = _flash_fwd_scan(q, k, v, q_pos, k_pos, window, kv_chunk,
                               s_low_precision)
    B, Sq, H, _ = q.shape
    res = (q, k, v, q_pos, k_pos, out, lse)
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype), res


def _flash_bwd(window, kv_chunk, s_low_precision, res, dout):
    """FlashAttention backward: recompute p per chunk from saved lse —
    O(seq) residual memory instead of per-chunk probability matrices."""
    q, k, v, q_pos, k_pos, out, lse = res
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = D ** -0.5
    kc, vc, pc, pad = _chunk_kv(k, v, k_pos, kv_chunk)

    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, D)
    do = dout.astype(jnp.float32).reshape(B, Sq, KH, G, v.shape[-1])
    delta = jnp.sum(do * out, axis=-1).transpose(0, 2, 3, 1)   # (B,KH,G,Sq)

    dq0 = jnp.zeros((B, Sq, KH, G, D), jnp.float32)

    def body(dq, xs):
        ki, vi, pi = xs
        kif = ki.astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kif) * scale
        valid = _mask(q_pos, pi, window)
        p = jnp.exp(s - lse[..., None]) * valid[None, None, None]
        # bf16 probability/score-grad tiles on the matmul paths (f32 accum)
        pb = p.astype(ki.dtype)
        dv_c = jnp.einsum("bhgqk,bqhgd->bkhd", pb, do.astype(ki.dtype),
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", do.astype(vi.dtype), vi,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dsb = ds.astype(ki.dtype)
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", dsb, ki,
                             preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", dsb, qf.astype(ki.dtype),
                          preferred_element_type=jnp.float32)
        return dq, (dk_c, dv_c)

    dq, (dk_c, dv_c) = jax.lax.scan(body, dq0, (kc, vc, pc))
    n = dk_c.shape[0]
    dk = dk_c.transpose(1, 0, 2, 3, 4).reshape(B, n * kv_chunk, KH, D)[:, :Sk]
    dv = dv_c.transpose(1, 0, 2, 3, 4).reshape(B, n * kv_chunk, KH,
                                               v.shape[-1])[:, :Sk]
    import numpy as np
    zero_i = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dq.reshape(B, Sq, H, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), zero_i(q_pos), zero_i(k_pos))


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def online_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                     kv_chunk: int = 512, q_chunk: int = 0,
                     causal_prefix: bool = False,
                     s_low_precision: bool = False) -> jax.Array:
    """Flash-style online-softmax attention (custom-VJP; never materializes
    the (Sq, Sk) score matrix in forward OR backward).

    q: (B, Sq, H, D); k, v: (B, Sk, KH, D); *_pos absolute positions
    ((Sq,), (Sk,)).  ``causal_prefix=True`` asserts q_pos == k_pos ==
    arange (plain causal self-attention): the query-blocked path then only
    visits the reachable KV prefix per block — skipping the fully-masked
    upper-triangle tiles halves the quadratic work the scan version wastes.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]

    if q_chunk and Sq > q_chunk and Sq % q_chunk == 0:
        nq = Sq // q_chunk
        qb = q.reshape(B, nq, q_chunk, H, D)
        pb = q_pos.reshape(nq, q_chunk)

        if causal_prefix and Sq == Sk:
            outs = []
            for i in range(nq):
                lo = max(0, (i + 1) * q_chunk - window) if window else 0
                lo = (lo // kv_chunk) * kv_chunk        # chunk-aligned
                hi = (i + 1) * q_chunk
                outs.append(_flash_attention(
                    qb[:, i], k[:, lo:hi], v[:, lo:hi], pb[i], k_pos[lo:hi],
                    window, min(kv_chunk, hi - lo), s_low_precision))
            return jnp.concatenate(outs, axis=1)

        def _one(args):
            qi, pi = args
            return _flash_attention(qi, k, v, pi, k_pos, window,
                                    min(kv_chunk, Sk), s_low_precision)

        out = jax.lax.map(_one, (qb.transpose(1, 0, 2, 3, 4), pb))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, v.shape[-1])

    return _flash_attention(q, k, v, q_pos, k_pos, window,
                            min(kv_chunk, Sk), s_low_precision)


def _kernel_covers(q, k, v, causal_prefix: bool, s_low_precision: bool) -> bool:
    """Whether ``kernels.flash_attention.flash_attention_train`` computes
    this call: causal self-attention over positions 0..S-1 (what
    ``causal_prefix`` asserts), any window, f32 or bf16 operands of one
    dtype, and q/k and v head sizes the MXU tiles take.
    ``s_low_precision`` asks for bf16-accumulated scores, which the
    kernel does not do."""
    dtypes = {q.dtype, k.dtype, v.dtype}
    return (causal_prefix and q.shape[1] == k.shape[1]
            and not s_low_precision
            and len(dtypes) == 1 and dtypes <= {jnp.dtype(jnp.float32),
                                                jnp.dtype(jnp.bfloat16)}
            and all(x.shape[-1] % 8 == 0 and x.shape[-1] <= 256
                    for x in (q, v)))


def run_attention(q, k, v, q_pos, k_pos, *, impl: str = "chunked",
                  window: int = 0, kv_chunk: int = 512,
                  q_chunk: int = 0, causal_prefix: bool = False,
                  s_low_precision: bool = False) -> jax.Array:
    """``impl="chunked"`` takes the fused flash-attention kernels (forward
    and backward) where the backend runs them natively and the call is one
    they cover (``_kernel_covers``).  Otherwise, and on CPU, the jnp path
    below runs: ``kv_chunk`` and ``q_chunk`` only shape it."""
    if impl == "naive":
        return naive_attention(q, k, v, q_pos, k_pos, window)

    def _jnp():
        if k.shape[1] <= kv_chunk and q_chunk == 0 and not s_low_precision:
            # degenerate chunking: the whole KV fits in one chunk, so the
            # online-softmax scan buys nothing and its backward's per-chunk
            # probability recompute is pure extra arithmetic — the direct
            # form is exact attention over the same mask and lets XLA keep
            # p for the backward (score matrix is <= one chunk wide)
            return naive_attention(q, k, v, q_pos, k_pos, window)
        return online_attention(q, k, v, q_pos, k_pos, window=window,
                                kv_chunk=kv_chunk, q_chunk=q_chunk,
                                causal_prefix=causal_prefix,
                                s_low_precision=s_low_precision)

    if not _kernel_covers(q, k, v, causal_prefix, s_low_precision):
        return _jnp()
    from ..kernels.flash_attention import flash_attention_train
    return flash_attention_train(q, k, v, window=window, ref=_jnp)


# ---------------------------------------------------------------------------
# block-level entry points
# ---------------------------------------------------------------------------

# RMSNorm of the kv latent: DeepSeek's kv_a_layernorm keeps its module
# default eps, not the config's rms_norm_eps
MLA_LATENT_EPS = 1e-6


def init_mla(cfg, key, dtype) -> dict:
    """Multi-head latent attention without a query low-rank (DeepSeek-V3 at
    ``q_lora_rank`` null): q_proj, kv_a_proj (latent + the shared rotary
    key), the latent's RMSNorm, kv_b_proj (per-head k and v from the
    latent), o_proj; no biases."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "q_proj": init_dense(ks[0], d, h * (dn + dr), dtype),
        "kv_a_proj": init_dense(ks[1], d, r + dr, dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "kv_b_proj": init_dense(ks[2], r, h * (dn + dv), dtype),
        "o_proj": init_dense(ks[3], h * dv, d, dtype),
    }


def _rope_pairs(x, positions, theta):
    """DeepSeek's rotary layout: the rotated dims come as adjacent pairs,
    de-interleaved into halves and then rotated as ``apply_rope`` does."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def mla_attention(cfg, p, x, positions, *, lora=None, lora_scale=1.0,
                  impl="chunked", kv_chunk=512, q_chunk=0,
                  dense_impl: str = "einsum"):
    """Causal MLA over a full sequence (training).  LoRA adapts
    ``q_proj`` and ``kv_b_proj``.  q and k heads are qk_nope + qk_rope
    wide (softmax scale their width^-1/2), v heads v_head_dim wide: the
    attention kernels take the two widths apart."""
    B, S, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def _l(name):
        return None if lora is None or name not in lora else lora[name]

    q = dense(x, p["q_proj"]["w"], None, _l("q_proj"), lora_scale,
              impl=dense_impl).reshape(B, S, h, dn + dr)
    ckv = dense(x, p["kv_a_proj"]["w"], impl=dense_impl)
    c = rmsnorm(ckv[..., :r], p["kv_norm"]["scale"], MLA_LATENT_EPS)
    kv = dense(c, p["kv_b_proj"]["w"], None, _l("kv_b_proj"), lora_scale,
               impl=dense_impl).reshape(B, S, h, dn + dv)
    pos = jnp.broadcast_to(positions, (B, S))
    q_pe = _rope_pairs(q[..., dn:], pos, cfg.rope_theta)
    k_pe = _rope_pairs(ckv[:, :, None, r:], pos, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, h, dr))], axis=-1)
    o = run_attention(q, k, kv[..., dn:], positions, positions, impl=impl,
                      kv_chunk=kv_chunk, q_chunk=q_chunk, causal_prefix=True)
    return dense(o.reshape(B, S, h * dv), p["o_proj"]["w"], impl=dense_impl)


def self_attention(cfg, p, x, positions, *, lora=None, lora_scale=1.0,
                   impl="chunked", kv_chunk=512, q_chunk=0,
                   return_cache=False, cache_len: int = 0,
                   s_low_precision: bool = False, dense_impl: str = "einsum"):
    """Causal self-attention over a full sequence (train / prefill).

    positions: (S,) absolute positions.  If ``return_cache``, also returns a
    decode cache of length ``cache_len or S`` (ring-windowed when
    cfg.attn_window is set and smaller).
    """
    B, S, _ = x.shape
    q, k, v = _proj_qkv(cfg, p, x, lora, lora_scale, dense_impl)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)
        k = apply_rope(k, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)
    o = run_attention(q, k, v, positions, positions, impl=impl,
                      window=cfg.attn_window, kv_chunk=kv_chunk,
                      q_chunk=q_chunk, causal_prefix=True,
                      s_low_precision=s_low_precision)
    y = dense(o.reshape(B, S, -1), p["wo"]["w"], p["wo"].get("b"),
              None if lora is None or "o" not in lora else lora["o"], lora_scale,
              impl=dense_impl, w_scale=p["wo"].get("w_scale"))
    if not return_cache:
        return y
    L = cache_len or S
    if cfg.attn_window:
        L = min(L, cfg.attn_window)
    if L >= S:
        kc = jnp.pad(k, ((0, 0), (0, L - S), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, L - S), (0, 0), (0, 0)))
        pc = jnp.pad(positions, (0, L - S), constant_values=-1)
    else:
        # keep the trailing window, laid out ring-buffer style so that
        # slot(p) == p % L matches decode_attention's write rule
        shift = (S - L) % L
        kc = jnp.roll(k[:, S - L:], shift, axis=1)
        vc = jnp.roll(v[:, S - L:], shift, axis=1)
        pc = jnp.roll(positions[S - L:], shift)
    # per-sequence position rows: every sequence in a prefill batch shares
    # the layout, but decode advances each row independently (serving slots)
    cache = {"k": kc, "v": vc, "pos": jnp.broadcast_to(pc, (B, pc.shape[0]))}
    return y, cache


def init_attn_cache(cfg, batch: int, cache_len: int, dtype) -> dict:
    L = cache_len
    if cfg.attn_window:
        L = min(L, cfg.attn_window)
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, L, kh, hd), dtype),
        "v": jnp.zeros((batch, L, kh, hd), dtype),
        "pos": jnp.full((batch, L), -1, jnp.int32),
    }


def init_paged_attn_cache(cfg, num_pages: int, page_size: int, dtype) -> dict:
    """Global KV page pool: (KH, NP, PS, D) per k/v.  Page 0 is the null
    page — dead slots write there and the allocator never hands it out.
    Unlike the slab cache there is no per-slot "pos" row: block tables and
    live lengths are engine state shared by every layer."""
    if cfg.attn_window:
        raise NotImplementedError(
            "paged KV assumes a length-contiguous logical view; ring-wrapped "
            "sliding-window caches keep the slab layout")
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((kh, num_pages, page_size, hd), dtype),
        "v": jnp.zeros((kh, num_pages, page_size, hd), dtype),
    }


def paged_decode_attention(cfg, p, x, cache, block_table, cur_index, *,
                           lora=None, lora_scale=1.0, impl="naive",
                           dense_impl: str = "einsum", adapter_idx=None):
    """One-token decode over the paged pool: x (B, 1, d); cache {"k","v"}
    (KH, NP, PS, D); block_table (B, MP) page ids; cur_index (B,) absolute
    positions (each serving slot at its own).

    Writes the new KV into page ``block_table[b, pos // PS]`` at offset
    ``pos % PS`` (dead slots hit the null page 0) and attends over the
    slot's logical view.  ``impl="flash"`` routes through
    ``kernels.flash_attention.paged_decode`` — the scalar-prefetch Pallas
    gather kernel on TPU, the jnp gather oracle elsewhere; any other impl
    forces the oracle (whole-gather einsum GSPMD can shard).

    ``adapter_idx`` (B,) makes every LoRA-adapted projection multi-tenant:
    lora leaves become (A, ...) pools and slot b wears adapter
    ``adapter_idx[b]`` (see ``layers.dense``).
    """
    B = x.shape[0]
    PS = cache["k"].shape[2]
    MP = block_table.shape[1]
    q, k, v = _proj_qkv(cfg, p, x, lora, lora_scale, dense_impl, adapter_idx)
    pos_vec = jnp.broadcast_to(jnp.asarray(cur_index, jnp.int32), (B,))
    pos = pos_vec[:, None]
    if cfg.pos_emb == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    bidx = jnp.arange(B)
    # dead slots can sit one past the table (pos == max_len); their row is
    # all-null anyway — clamp so the gather stays in bounds by construction
    page = block_table[bidx, jnp.minimum(pos_vec // PS, MP - 1)]
    off = pos_vec % PS
    kc = cache["k"].at[:, page, off].set(
        k[:, 0].astype(cache["k"].dtype).transpose(1, 0, 2))
    vc = cache["v"].at[:, page, off].set(
        v[:, 0].astype(cache["v"].dtype).transpose(1, 0, 2))
    from ..kernels.flash_attention import paged_decode
    o = paged_decode(q, kc, vc, pos_vec + 1, block_table,
                     use_kernel=None if impl == "flash" else False)
    y = dense(o.reshape(B, 1, -1), p["wo"]["w"], p["wo"].get("b"),
              None if lora is None or "o" not in lora else lora["o"], lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["wo"].get("w_scale"))
    return y, {"k": kc, "v": vc}


def paged_chunk_attention(cfg, p, x, cache, block_table, start, *,
                          lora=None, lora_scale=1.0,
                          dense_impl: str = "einsum"):
    """One chunked-prefill step: x (1, C, d) with C == page_size — the
    chunk covering absolute positions [start, start + C); block_table
    (MP,) the slot's page row, the chunk's own page already allocated.

    Writes the whole chunk's KV into page ``block_table[start // PS]``
    with ONE dynamic_update_slice (chunk == page by construction), then
    attends causally over the gathered logical view — entry i of the
    gather IS absolute position i, so the mask is plain
    ``k_idx <= q_pos``.  Padded tail queries (beyond the prompt) produce
    garbage the caller never reads, and their KV is overwritten in place
    as decode advances through the same page.  Stays on the jnp gather
    form: chunk prefill is off the steady-state path the Pallas kernel
    serves."""
    B, C, _ = x.shape
    KH, _, PS, D = cache["k"].shape
    MP = block_table.shape[0]
    q, k, v = _proj_qkv(cfg, p, x, lora, lora_scale, dense_impl)
    positions = start + jnp.arange(C, dtype=jnp.int32)
    if cfg.pos_emb == "rope":
        bpos = jnp.broadcast_to(positions, (B, C))
        q = apply_rope(q, bpos, cfg.rope_theta)
        k = apply_rope(k, bpos, cfg.rope_theta)
    page = block_table[start // PS]
    kc = jax.lax.dynamic_update_slice(
        cache["k"], k[0].astype(cache["k"].dtype).transpose(1, 0, 2)[:, None],
        (0, page, 0, 0))
    vc = jax.lax.dynamic_update_slice(
        cache["v"], v[0].astype(cache["v"].dtype).transpose(1, 0, 2)[:, None],
        (0, page, 0, 0))
    kg = kc[:, block_table].reshape(KH, MP * PS, D)
    vg = vc[:, block_table].reshape(KH, MP * PS, D)
    G = q.shape[2] // KH
    qr = q[0].reshape(C, KH, G, D)
    s = jnp.einsum("qhgd,hkd->hgqk", qr.astype(jnp.float32),
                   kg.astype(jnp.float32)) * D ** -0.5
    k_idx = jnp.arange(MP * PS)
    mask = k_idx[None, :] <= positions[:, None]              # (C, MP*PS)
    s = jnp.where(mask[None, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,hkd->qhgd", pr, vg.astype(jnp.float32))
    o = o.reshape(1, C, -1).astype(x.dtype)
    y = dense(o, p["wo"]["w"], p["wo"].get("b"),
              None if lora is None or "o" not in lora else lora["o"], lora_scale,
              impl=dense_impl, w_scale=p["wo"].get("w_scale"))
    return y, {"k": kc, "v": vc}


def decode_masked_attention(q, k, v, q_pos, k_pos, window: int = 0):
    """Whole-score decode attention with PER-SLOT positions.

    q: (B, 1, H, D); k/v: (B, L, KH, D); q_pos (B,); k_pos (B, L) absolute
    positions (-1 = empty).  The (B, H, 1, L) score einsum stays whole so
    GSPMD can shard the cache sequence dim; it is also the exact oracle
    for ``kernels.flash_attention.flash_decode`` — correct for ring-wrapped
    windowed caches, where the length-masked kernel is not.
    """
    B, _, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qr = q.reshape(B, 1, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    m = (k_pos <= q_pos[:, None]) & (k_pos >= 0)
    if window:
        m &= (q_pos[:, None] - k_pos) < window
    s = jnp.where(m[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D).astype(q.dtype)


def decode_attention(cfg, p, x, cache, cur_index, *, lora=None,
                     lora_scale=1.0, impl="naive",
                     dense_impl: str = "einsum", adapter_idx=None):
    """One-token decode: x (B, 1, d); cur_index absolute position, scalar
    int32 OR a per-sequence (B,) vector (continuous-batching slots each at
    their own position).

    Writes the new KV at slot ``cur_index % L`` per sequence (ring buffer
    when windowed) and attends over the whole cache.  ``impl="flash"``
    routes through ``kernels.flash_attention.flash_decode`` — the split-K
    Pallas kernel on TPU (per-slot live-length tile skipping), the same
    masked einsum as "naive" elsewhere; ring-wrapped windowed caches are
    not length-contiguous, so they always take the position-masked path.
    """
    B = x.shape[0]
    L = cache["k"].shape[1]
    q, k, v = _proj_qkv(cfg, p, x, lora, lora_scale, dense_impl, adapter_idx)
    pos_vec = jnp.broadcast_to(jnp.asarray(cur_index, jnp.int32), (B,))
    pos = pos_vec[:, None]
    if cfg.pos_emb == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    bidx = jnp.arange(B)
    slot = jnp.mod(pos_vec, L)
    kc = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    vc = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    pc = cache["pos"].at[bidx, slot].set(pos_vec)
    if impl == "flash" and not cfg.attn_window:
        from ..kernels.flash_attention import flash_decode
        o = flash_decode(q, kc, vc, pos_vec + 1, window=0)
    else:
        o = decode_masked_attention(q, kc, vc, pos_vec, pc, cfg.attn_window)
    y = dense(o.reshape(B, 1, -1), p["wo"]["w"], p["wo"].get("b"),
              None if lora is None or "o" not in lora else lora["o"], lora_scale,
              impl=dense_impl, adapter_idx=adapter_idx,
              w_scale=p["wo"].get("w_scale"))
    return y, {"k": kc, "v": vc, "pos": pc}
