"""Entries of the attention kernels: the (B, S, H, D) model layout -> the
kernels' merged-head layout (a reshape) + padding, backend dispatch, and the
training custom VJP."""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import backend
from .decode import flash_decode_kernel, flash_decode_q8_kernel
from .kernel import flash_attention_bwd_kernels, flash_attention_kernel
from .paged_decode import paged_decode_kernel, paged_decode_q8_kernel
from .ref import (flash_attention_ref, flash_decode_q8_ref, flash_decode_ref,
                  paged_decode_q8_ref, paged_decode_ref)
from .tune import best_decode_block, best_paged_block, best_train_blocks


def _pad_seq(x, n: int):
    return jnp.pad(x, ((0, 0), (0, n), (0, 0))) if n else x


def _oracle(q, k, v, window: int):
    """``flash_attention_ref`` on the model layout."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(flash_attention_ref(t(q), t(k), t(v), window=window))


# ---------------------------------------------------------------------------
# training: forward + backward kernels behind one custom VJP
# ---------------------------------------------------------------------------

class _TrainCfg(NamedTuple):
    """Static kernel config: the custom VJP's nondiff argument."""
    head_dim: int
    window: int
    seq_k: int
    q_offset: int
    bq: int
    bk: int
    kv_heads: int
    mxu_dtype: str
    interpret: bool

    def kw(self) -> dict:
        kw = self._asdict()
        kw["mxu_dtype"] = jnp.dtype(self.mxu_dtype)
        return kw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_train(cfg: _TrainCfg, q, k, v):
    return flash_attention_kernel(q, k, v, **cfg.kw())[0]


def _flash_train_fwd(cfg: _TrainCfg, q, k, v):
    o, lse = flash_attention_kernel(q, k, v, **cfg.kw())
    return o, (q, k, v, o, lse)


def _flash_train_bwd(cfg: _TrainCfg, res, do):
    q, k, v, o, lse = res
    B, S, HD = q.shape
    delta = jnp.sum((o.astype(jnp.float32) * do.astype(jnp.float32)).reshape(
        B, S, HD // cfg.head_dim, cfg.head_dim), axis=-1)
    return flash_attention_bwd_kernels(q, k, v, do, lse,
                                       delta.transpose(0, 2, 1)[:, :, None],
                                       **cfg.kw())


_flash_train.defvjp(_flash_train_fwd, _flash_train_bwd)


def mxu_dtype_for(dtype):
    """Operand dtype of the training kernels' tile matmuls: bfloat16 for
    float32 inputs at the default matmul precision (what XLA's own f32
    dots do on a TPU), the input dtype otherwise."""
    default = jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16", "fastest")
    if jnp.dtype(dtype) == jnp.float32 and default:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(dtype)


def flash_attention_train(q, k, v, *, window: int = 0,
                          ref: "Optional[Callable[[], jax.Array]]" = None,
                          bq: "int | None" = None, bk: "int | None" = None,
                          kv_heads: "int | None" = None,
                          interpret: "bool | None" = None,
                          use_kernel: "bool | None" = None):
    """Differentiable causal GQA attention for training and prefill.

    q: (B, Sq, H, D); k/v: (B, Sk, KH, D) — the model layout — with q
    aligned at the end of k (q row r at position Sk - Sq + r).  On the
    kernel path the forward and both backward passes are Pallas kernels
    behind one custom VJP, on the model layout with the heads merged (no
    transpose): no (Sq, Sk) tensor reaches HBM, and the backward keeps q,
    k, v, the output and the f32 log-sum-exp.

    Dispatch follows ``kernels.backend.dispatch`` under the name
    ``flash_attention_train``: the kernels on TPU, ``ref`` (a zero-argument
    thunk; default the f32 jnp oracle) elsewhere, and an explicit
    ``interpret`` forces the kernels.  Blocks default to
    ``tune.best_train_blocks``; the tile matmuls take ``mxu_dtype_for``
    operands."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]

    def _ref():
        return ref() if ref is not None else _oracle(q, k, v, window)

    def _kern(interp: bool):
        tq, tk, tkv = best_train_blocks(B, H, KH, Sq, Sk, D, q.dtype)
        tq, tk, tkv = bq or tq, bk or tk, kv_heads or tkv
        pq, pk = (-Sq) % tq, (-Sk) % tk
        cfg = _TrainCfg(int(D), int(window), int(Sk), max(Sk - Sq, 0),
                        int(tq), int(tk), int(tkv),
                        mxu_dtype_for(q.dtype).name,
                        bool(interp))
        o = _flash_train(cfg, _pad_seq(q.reshape(B, Sq, H * D), pq),
                         _pad_seq(k.reshape(B, Sk, KH * D), pk),
                         _pad_seq(v.reshape(B, Sk, KH * D), pk))
        return o[:, :Sq].reshape(B, Sq, H, D)

    return backend.dispatch("flash_attention_train", kernel=_kern, ref=_ref,
                            interpret=interpret, use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret",
                                             "use_kernel"))
def flash_decode(q, k, v, lengths, *, window: int = 0,
                 k_scale=None, v_scale=None,
                 bk: "int | None" = None, interpret: "bool | None" = None,
                 use_kernel: "bool | None" = None):
    """One-token decode attention over per-slot KV caches.

    q: (B, 1, H, D) or (B, H, D); k/v: (B, L, KH, D) — the model cache
    layout of ``repro.models.attention``; lengths: (B,) int32 live entries
    per slot (entries contiguous at [0, length); callers with ring-wrapped
    windowed caches must use the position-masked path instead).

    ``k_scale``/``v_scale`` (f32 ``(KH,)`` per-KV-head, from
    ``repro.precision.quantize_kv_int8``) switch on the int8-KV cache:
    k/v are then int8 and dequantized per-tile in VMEM by the q8 kernel
    (jnp oracle off-TPU).

    Dispatch mirrors ``lora_matmul`` through the shared
    ``kernels.backend.dispatch``: the native split-K Pallas kernel on
    TPU (block size from the memoized ``tune.best_decode_block``), the
    masked-einsum oracle elsewhere — an explicit ``interpret`` flag forces
    the kernel (interpret-mode parity testing)."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    B, H, D = q.shape
    L, KH = k.shape[1], k.shape[2]
    G = H // KH
    qt = q.reshape(B, KH, G, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    quantized = k_scale is not None

    def _ref():
        if quantized:
            return flash_decode_q8_ref(qt, kt, vt, k_scale, v_scale,
                                       lengths, window=window)
        return flash_decode_ref(qt, kt, vt, lengths, window=window)

    def _kern(interp: bool):
        tbk = bk
        if tbk is None:
            tbk = best_decode_block(B, KH, G, L, D, q.dtype,
                                    kv_dtype=k.dtype if quantized else None)
        tbk = min(tbk, L)
        pk = (-L) % tbk
        kp, vp = kt, vt
        if pk:       # padded tail entries sit beyond every live length
            kp = jnp.pad(kp, ((0, 0), (0, 0), (0, pk), (0, 0)))
            vp = jnp.pad(vp, ((0, 0), (0, 0), (0, pk), (0, 0)))
        if quantized:
            return flash_decode_q8_kernel(qt, kp, vp, lengths, k_scale,
                                          v_scale, window=window, bk=tbk,
                                          interpret=interp)
        return flash_decode_kernel(qt, kp, vp, lengths, window=window,
                                   bk=tbk, interpret=interp)

    o = backend.dispatch("flash_decode", kernel=_kern, ref=_ref,
                         interpret=interpret, use_kernel=use_kernel)
    o = o.reshape(B, H, D)
    return o[:, None] if squeeze else o


@functools.partial(jax.jit, static_argnames=("bk", "interpret", "use_kernel"))
def paged_decode(q, k_pages, v_pages, lengths, block_tables, *,
                 k_scale=None, v_scale=None,
                 bk: "int | None" = None, interpret: "bool | None" = None,
                 use_kernel: "bool | None" = None):
    """One-token decode attention over a block-table PAGED KV cache.

    q: (B, 1, H, D) or (B, H, D) — the model layout; k_pages/v_pages:
    (KH, NP, PS, D) global page pool; block_tables: (B, MP) int32 page
    ids per slot (0 = null page); lengths: (B,) int32 live entries per
    slot (contiguous in the logical [0, MP*PS) view).

    ``k_scale``/``v_scale`` (f32 ``(KH,)`` per-KV-head) switch on the
    int8 page pool — half the KV HBM of bf16 — dequantized per-tile in
    VMEM by the q8 kernel (jnp oracle off-TPU).

    Dispatch mirrors ``flash_decode`` through the shared
    ``kernels.backend.dispatch``: the native scalar-prefetch Pallas
    kernel on TPU (the block-table gather IS the kv index map; tile size
    from the memoized ``tune.best_paged_block``), the jnp gather oracle
    elsewhere — an explicit ``interpret`` flag forces the kernel
    (interpret-mode parity testing)."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    B, H, D = q.shape
    KH, _, PS, _ = k_pages.shape
    MP = block_tables.shape[1]
    G = H // KH
    qt = q.reshape(B, KH, G, D)
    quantized = k_scale is not None

    def _ref():
        if quantized:
            return paged_decode_q8_ref(qt, k_pages, v_pages, k_scale,
                                       v_scale, lengths, block_tables)
        return paged_decode_ref(qt, k_pages, v_pages, lengths, block_tables)

    def _kern(interp: bool):
        tbk = bk
        if tbk is None:
            tbk = best_paged_block(
                B, KH, G, MP, PS, D, q.dtype,
                kv_dtype=k_pages.dtype if quantized else None)
        if quantized:
            return paged_decode_q8_kernel(qt, k_pages, v_pages, lengths,
                                          block_tables, k_scale, v_scale,
                                          bk=tbk, interpret=interp)
        return paged_decode_kernel(qt, k_pages, v_pages, lengths,
                                   block_tables, bk=tbk, interpret=interp)

    o = backend.dispatch("paged_decode", kernel=_kern, ref=_ref,
                         interpret=interpret, use_kernel=use_kernel)
    o = o.reshape(B, H, D)
    return o[:, None] if squeeze else o
