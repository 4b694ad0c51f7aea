"""FlashAttention forward and backward Pallas kernels (TPU target, GQA-aware).

The kernels read and write the model's own layout, so no transpose is
needed around them: q, o and their gradients are (B, Sq, H*D), k and v
(B, Sk, KH*D) — ``(B, S, H, D)`` with the heads merged, a free reshape.
One grid step covers ``kv_heads`` KV heads and the ``kv_heads * G`` query
heads that read them (G = H // KH): GQA is folded into the BlockSpec index
maps, query lane block j and KV lane block j covering the same head group.
The log-sum-exp and the backward's row sums are f32 rows (B, H, 1, Sq).

* forward (``flash_attention_kernel``): grid (B, KH/kv_heads, Sq/bq,
  Sk/bk), Sk innermost.  VMEM scratch carries the online-softmax state (m
  and l as rows) and the f32 output accumulator across Sk steps; no score
  tile leaves VMEM.
* backward (``flash_attention_bwd_kernels``): a dK/dV kernel (grid over KV
  blocks, q blocks innermost, dK/dV accumulated in VMEM) and a dQ kernel
  (grid over q blocks, KV blocks innermost).  Both recompute p per tile
  from q, k and the log-sum-exp.  Where the sequence is one block each
  way, one kernel (``attn_bwd``) makes all three gradients from one p.

The forward and dK/dV kernels work on transposed (k, q) tiles, so the row
statistics broadcast over sublanes and the forward's max and sum reduce
over sublanes; the dQ kernel takes (q, k) tiles and turns its two rows into
lane-replicated columns once per sub-tile.

Masking is causal + sliding window from absolute positions (q row r sits
at ``q_offset + r``, k row c at c; k rows at or past ``seq_k`` are
padding).  Plain causal self-attention over square blocks (``bq == bk``,
``q_offset == 0``, no window) takes the fast path: a block below the
diagonal is worked in 128 x 128 sub-tiles with no mask at all, the
diagonal block skips the sub-tiles above its diagonal and masks only the
ones on it, and blocks above it are skipped.  Any other call masks every
tile whole.  A block no query reaches repeats the last block read in its
index map, so it costs no DMA either.

The loops over heads and sub-tiles are unrolled: each sub-tile is
independent work the compiler can overlap (rolled into ``lax.fori_loop``
the kernels ran at about 40% of this speed on a TPU v5e).  The unrolled
body is slow to trace and lower, which every start pays, so the block
rule (``tune.best_train_blocks``) keeps head groups small, and each
``pallas_call`` is traced once per configuration and argument types and
replayed after (``_traced``): a round's trace meets each kernel more than
once (a custom VJP's primal and its forward rule).

q and k heads are ``head_dim`` wide and v heads ``v_dim`` (default the
same; latent attention's differ): the output, dO and dV take v's width.

``mxu_dtype`` is the operand dtype of the tile matmuls: q (scaled by
D^-1/2 first), k, v, p, dO and dS are cast to it in VMEM; accumulation and
the softmax statistics stay f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import jaxpr_as_fun

NEG_INF = -1e30
_LANES = 128
_SUB = 128                              # fast-path sub-tile edge
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b


def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


def _fast(Sq, Sk, bq, bk, q_offset, window) -> bool:
    return Sq == Sk and bq == bk and q_offset == 0 and not window


def _sub(block: int, fast: bool) -> int:
    return _SUB if fast and block % _SUB == 0 else block


def _reachable(q_lo, k_lo, bq, bk, window, seq_k):
    """Whether tile (q rows from q_lo, k rows from k_lo) has any unmasked
    entry."""
    r = jnp.logical_and(k_lo <= q_lo + bq - 1, k_lo < seq_k)
    if window:
        r = jnp.logical_and(r, q_lo - (k_lo + bk - 1) < window)
    return r


def _mask(q_lo, k_lo, shape, q_axis, window, seq_k, causal=True):
    """Bool tile of allowed (q, k) pairs; q positions run along ``q_axis``."""
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    m = k_pos < seq_k
    if causal:
        m &= k_pos <= q_pos
    if window:
        m &= (q_pos - k_pos) < window
    return m


def _on_tiles(body, qi, kj, *, fast, steps, q_offset, bq, bk, window,
              seq_k):
    """Run ``body(kind)`` for the tile (q block qi, KV block kj): "full"
    below the diagonal and "diag" on it (fast path), "masked" for any
    reachable tile otherwise.  On the fast path ``steps`` (the blocks per
    sequence) of 1 leaves only the diagonal tile, and only it is traced."""
    if fast and steps == 1:
        body("diag")
    elif fast:
        pl.when(kj < qi)(lambda: body("full"))
        pl.when(kj == qi)(lambda: body("diag"))
    else:
        pl.when(_reachable(q_offset + qi * bq, kj * bk, bq, bk, window,
                           seq_k))(lambda: body("masked"))


def _sub_mask(kind, a, b, q_lo, k_lo, tq, tk, q_axis, window, seq_k,
              padded):
    """Mask of sub-tile (q sub-tile a, k sub-tile b) of a tile, or None
    where it needs none.  On the fast path's diagonal only sub-tile a == b
    is causal-masked; padding keys can only sit in the diagonal tile."""
    shape = (tq, tk) if q_axis == 0 else (tk, tq)
    if kind == "masked":
        return _mask(q_lo + a * tq, k_lo + b * tk, shape, q_axis, window,
                     seq_k)
    if kind == "diag" and (a == b or padded):
        return _mask(q_lo + a * tq, k_lo + b * tk, shape, q_axis, 0, seq_k,
                     causal=a == b)
    return None


# (kernel, configuration, argument types, matmul precision) -> the traced
# pallas_call, replayed on every later call
_TRACED: dict = {}


def _traced(scope: str, config: tuple, make, *args):
    """``make()(*args)``, one ``pallas_call``, in the named scope ``scope``
    (which names its instruction in the compiled program).  Traced once
    per key and replayed after, so the kernel body is not traced again;
    the precision is in the key because the body's dots take it when
    traced."""
    key = (scope, config, tuple(jax.typeof(a) for a in args),
           jax.config.jax_default_matmul_precision)
    closed = _TRACED.get(key)
    if closed is None:
        closed = _TRACED[key] = jax.make_jaxpr(lambda *a: make()(*a))(*args)
    with jax.named_scope(scope):
        return jaxpr_as_fun(closed)(*args)


def _row_to_col(row):
    """(1, n) f32 row -> (n, 128) with the row replicated along lanes."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[-1])))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                vt_ref, *, D: int, Dv: int, scale: float, bq: int, bk: int,
                k_steps: int, q_offset: int, window: int, seq_k: int,
                group: int, fast: bool, padded: bool, mxu_dtype):
    """Works on transposed (k, q) tiles: the softmax statistics reduce over
    sublanes (element-wise across vregs, where a reduction over lanes
    would be a rotate-and-combine per vreg) and stay rows, so the
    log-sum-exp is stored without a transpose; the accumulator is o^T,
    (D, bq), fed by v^T (transposed once per tile into ``vt_ref``)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    heads = q_ref.shape[2] // D
    tq, tk = _sub(bq, fast), _sub(bk, fast)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(kind):
        q_lo, k_lo = q_offset + qi * bq, kj * bk
        for c in range(vt_ref.shape[0]):
            vt_ref[c] = jnp.transpose(v_ref[0, :, c * Dv:(c + 1) * Dv].astype(
                jnp.float32)).astype(vt_ref.dtype)
        for h in range(heads):
            c = h // group
            for a in range(bq // tq):
                cols = slice(a * tq, (a + 1) * tq)
                q = (q_ref[0, cols, h * D:(h + 1) * D].astype(jnp.float32)
                     * scale).astype(mxu_dtype)
                m, l = m_ref[h, :, cols], l_ref[h, :, cols]
                acc = acc_ref[h, :, cols]
                for b in range(bk // tk):
                    if kind == "diag" and b > a:
                        continue
                    keys = slice(b * tk, (b + 1) * tk)
                    s_t = _dot(k_ref[0, keys, c * D:(c + 1) * D], q, _NT,
                               mxu_dtype)                      # (tk, tq)
                    mask = _sub_mask(kind, a, b, q_lo, k_lo, tq, tk, 1,
                                     window, seq_k, padded)
                    if mask is not None:
                        s_t = jnp.where(mask, s_t, NEG_INF)
                    m_new = jnp.maximum(m, jnp.max(s_t, axis=0, keepdims=True))
                    p_t = jnp.exp(s_t - m_new)
                    if kind == "masked":       # rows with no key in the tile
                        p_t = jnp.where(mask, p_t, 0.0)
                    alpha = jnp.exp(m - m_new)
                    l = l * alpha + jnp.sum(p_t, axis=0, keepdims=True)
                    acc = acc * alpha + _dot(vt_ref[c, :, keys], p_t, _NN,
                                             mxu_dtype)        # (Dv, tq)
                    m = m_new
                m_ref[h, :, cols], l_ref[h, :, cols] = m, l
                acc_ref[h, :, cols] = acc

    _on_tiles(tile, qi, kj, fast=fast, steps=k_steps, q_offset=q_offset,
              bq=bq, bk=bk, window=window, seq_k=seq_k)

    @pl.when(kj == k_steps - 1)
    def _finish():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)                # (1, bq)
            o_ref[0, :, h * Dv:(h + 1) * Dv] = jnp.transpose(
                acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[0, h] = m_ref[h] + jnp.log(l)


def _kv_block(i, j, *, bq, bk, q_offset, window):
    """KV block a (q block i, step j) grid point reads: clamped into the
    reachable range so skipped steps repeat a block (no new DMA)."""
    j = jnp.minimum(j, (q_offset + i * bq + bq - 1) // bk)
    if window:
        j = jnp.maximum(j, jnp.maximum(q_offset + i * bq - window + 1, 0) // bk)
    return j


def _q_block(j, i, *, bq, bk, q_offset, q_steps, window):
    """Q block a (KV block j, step i) grid point of dK/dV reads, clamped
    the same way."""
    i = jnp.maximum(i, jnp.minimum(jnp.maximum(j * bk - q_offset, 0) // bq,
                                   q_steps - 1))
    if window:
        hi = (j * bk + bk - 1 + window - 1 - q_offset) // bq
        i = jnp.minimum(i, jnp.maximum(hi, 0))
    return i


def _geometry(q, k, D, bq, bk, seq_k, q_offset, window):
    B, Sq, HD = q.shape
    Sk, KHD = k.shape[1], k.shape[2]
    H, KH = HD // D, KHD // D
    bq, bk = min(bq, Sq), min(bk, Sk)
    seq_k = seq_k or Sk
    if q_offset < 0:
        q_offset = max(seq_k - Sq, 0)
    return dict(B=B, Sq=Sq, Sk=Sk, H=H, KH=KH, G=H // KH, bq=bq, bk=bk,
                seq_k=seq_k, q_offset=q_offset,
                fast=_fast(Sq, Sk, bq, bk, q_offset, window),
                padded=seq_k < Sk)


def flash_attention_kernel(q, k, v, *, head_dim: int, v_dim: int = 0,
                           window: int = 0, seq_k: int = 0,
                           q_offset: int = -1, bq: int = 256, bk: int = 256,
                           kv_heads: int = 1, mxu_dtype=None,
                           interpret: bool = False):
    """q: (B, Sq, H*D); k: (B, Sk, KH*D); v: (B, Sk, KH*Dv) with D =
    ``head_dim`` and Dv = ``v_dim`` (default D), sequence dims divisible by
    the blocks (ops.py pads).  Causal; ``q_offset`` is the absolute
    position of q row 0 (default: aligned at the TRUE sequence end,
    seq_k - Sq).  Returns (o (B, Sq, H*Dv), lse f32 (B, H, 1, Sq))."""
    D, Dv = head_dim, v_dim or head_dim
    g = _geometry(q, k, D, bq, bk, seq_k, q_offset, window)
    B, bq, bk, G = g["B"], g["bq"], g["bk"], g["G"]
    hb = kv_heads * G
    grid = (B, g["KH"] // kv_heads, g["Sq"] // bq, g["Sk"] // bk)
    kvb = functools.partial(_kv_block, bq=bq, bk=bk, q_offset=g["q_offset"],
                            window=window)
    q_spec = pl.BlockSpec((1, bq, hb * D), lambda b, h, i, j: (b, i, h))
    o_spec = pl.BlockSpec((1, bq, hb * Dv), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((1, bk, kv_heads * D),
                           lambda b, h, i, j: (b, kvb(i, j), h))
    v_spec = pl.BlockSpec((1, bk, kv_heads * Dv),
                          lambda b, h, i, j: (b, kvb(i, j), h))
    make = lambda: pl.pallas_call(
        functools.partial(_fwd_kernel, D=D, Dv=Dv, scale=D ** -0.5, bq=bq,
                          bk=bk, k_steps=grid[3], q_offset=g["q_offset"],
                          window=window, seq_k=g["seq_k"], group=G,
                          fast=g["fast"], padded=g["padded"],
                          mxu_dtype=mxu_dtype or q.dtype),
        grid=grid,
        in_specs=[q_spec, kv_spec, v_spec],
        out_specs=[o_spec,
                   pl.BlockSpec((1, hb, 1, bq),
                                lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, g["Sq"], g["H"] * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, g["H"], 1, g["Sq"]),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, 1, bq), jnp.float32),
                        pltpu.VMEM((hb, 1, bq), jnp.float32),
                        pltpu.VMEM((hb, Dv, bq), jnp.float32),
                        pltpu.VMEM((kv_heads, Dv, bk),
                                   mxu_dtype or q.dtype)],
        interpret=interpret,
    )
    config = (D, Dv, window, g["seq_k"], g["q_offset"], bq, bk, kv_heads,
              jnp.dtype(mxu_dtype or q.dtype).name, interpret)
    return _traced("attn_fwd", config, make, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *refs,
                D: int, Dv: int, scale: float, bq: int, bk: int, q_steps: int,
                q_offset: int, window: int, seq_k: int, group: int,
                fast: bool, padded: bool, fuse_dq: bool, mxu_dtype):
    """dK and dV of one KV block over the q blocks.  With ``fuse_dq`` (one
    block each way) it also makes dQ: dQ^T accumulates k^T dS^T per
    sub-tile, so p and dS are computed once for all three gradients."""
    if fuse_dq:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dqt_acc, kt_ref = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    kv_heads = k_ref.shape[2] // D
    tq, tk = _sub(bq, fast), _sub(bk, fast)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if fuse_dq:
            dqt_acc[...] = jnp.zeros_like(dqt_acc)

    def tile(kind):
        q_lo, k_lo = q_offset + qi * bq, kj * bk
        for c in range(kv_heads):
            lanes = slice(c * D, (c + 1) * D)
            if fuse_dq:
                kt_ref[...] = jnp.transpose(k_ref[0, :, lanes].astype(
                    jnp.float32)).astype(kt_ref.dtype)
            for b in range(bk // tk):
                keys = slice(b * tk, (b + 1) * tk)
                k = k_ref[0, keys, lanes]
                v = v_ref[0, keys, c * Dv:(c + 1) * Dv]
                dk, dv = dk_acc[c, keys], dv_acc[c, keys]
                for h in range(c * group, (c + 1) * group):
                    for a in range(bq // tq):
                        if kind == "diag" and a < b:
                            continue
                        rows = slice(a * tq, (a + 1) * tq)
                        q = (q_ref[0, rows, h * D:(h + 1) * D].astype(
                            jnp.float32) * scale).astype(mxu_dtype)
                        do = do_ref[0, rows, h * Dv:(h + 1) * Dv]
                        s_t = _dot(k, q, _NT, mxu_dtype)       # (tk, tq)
                        mask = _sub_mask(kind, a, b, q_lo, k_lo, tq, tk, 1,
                                         window, seq_k, padded)
                        if mask is not None:
                            s_t = jnp.where(mask, s_t, NEG_INF)
                        p_t = jnp.exp(s_t - lse_ref[0, h, :, rows])
                        if kind == "masked":
                            p_t = jnp.where(mask, p_t, 0.0)
                        dv = dv + _dot(p_t, do, _NN, mxu_dtype)
                        ds_t = p_t * (_dot(v, do, _NT, mxu_dtype)
                                      - di_ref[0, h, :, rows])
                        dk = dk + _dot(ds_t, q, _NN, mxu_dtype)
                        if fuse_dq:
                            dqt_acc[h, :, rows] += _dot(kt_ref[:, keys], ds_t,
                                                        _NN, mxu_dtype)
                dk_acc[c, keys], dv_acc[c, keys] = dk, dv

    _on_tiles(tile, qi, kj, fast=fast, steps=q_steps, q_offset=q_offset,
              bq=bq, bk=bk, window=window, seq_k=seq_k)

    @pl.when(qi == q_steps - 1)
    def _finish():
        for c in range(kv_heads):
            dk_ref[0, :, c * D:(c + 1) * D] = dk_acc[c].astype(dk_ref.dtype)
            dv_ref[0, :, c * Dv:(c + 1) * Dv] = dv_acc[c].astype(dv_ref.dtype)
        if fuse_dq:
            for h in range(dqt_acc.shape[0]):
                dq_ref[0, :, h * D:(h + 1) * D] = jnp.transpose(
                    dqt_acc[h] * scale).astype(dq_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               *, D: int, Dv: int, scale: float, bq: int, bk: int,
               k_steps: int,
               q_offset: int, window: int, seq_k: int, group: int,
               fast: bool, padded: bool, mxu_dtype):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    heads = q_ref.shape[2] // D
    tq, tk = _sub(bq, fast), _sub(bk, fast)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(kind):
        q_lo, k_lo = q_offset + qi * bq, kj * bk
        for h in range(heads):
            c = h // group
            for a in range(bq // tq):
                rows = slice(a * tq, (a + 1) * tq)
                q = (q_ref[0, rows, h * D:(h + 1) * D].astype(jnp.float32)
                     * scale).astype(mxu_dtype)
                do = do_ref[0, rows, h * Dv:(h + 1) * Dv]
                lse = _row_to_col(lse_ref[0, h, :, rows])[:, :1]
                di = _row_to_col(di_ref[0, h, :, rows])[:, :1]
                dq = dq_acc[h, rows]
                for b in range(bk // tk):
                    if kind == "diag" and b > a:
                        continue
                    keys = slice(b * tk, (b + 1) * tk)
                    k = k_ref[0, keys, c * D:(c + 1) * D]
                    s = _dot(q, k, _NT, mxu_dtype)
                    mask = _sub_mask(kind, a, b, q_lo, k_lo, tq, tk, 0,
                                     window, seq_k, padded)
                    if mask is not None:
                        s = jnp.where(mask, s, NEG_INF)
                    p = jnp.exp(s - lse)
                    if kind == "masked":
                        p = jnp.where(mask, p, 0.0)
                    ds = p * (_dot(do, v_ref[0, keys, c * Dv:(c + 1) * Dv],
                                   _NT, mxu_dtype) - di)
                    dq = dq + _dot(ds, k, _NN, mxu_dtype)
                dq_acc[h, rows] = dq

    _on_tiles(tile, qi, kj, fast=fast, steps=k_steps, q_offset=q_offset,
              bq=bq, bk=bk, window=window, seq_k=seq_k)

    @pl.when(kj == k_steps - 1)
    def _finish():
        for h in range(heads):
            dq_ref[0, :, h * D:(h + 1) * D] = (
                dq_acc[h] * scale).astype(dq_ref.dtype)


def flash_attention_bwd_kernels(q, k, v, do, lse, delta, *, head_dim: int,
                                v_dim: int = 0, window: int = 0,
                                seq_k: int = 0, q_offset: int = -1,
                                bq: int = 256, bk: int = 256,
                                kv_heads: int = 1, mxu_dtype=None,
                                interpret: bool = False):
    """dq, dk, dv of ``flash_attention_kernel``.  q: (B, Sq, H*D), do:
    (B, Sq, H*Dv); k: (B, Sk, KH*D), v: (B, Sk, KH*Dv); lse and delta =
    rowsum(do * o) per head: f32 (B, H, 1, Sq).  Blocks and masking as
    the forward."""
    D, Dv = head_dim, v_dim or head_dim
    g = _geometry(q, k, D, bq, bk, seq_k, q_offset, window)
    B, bq, bk, G = g["B"], g["bq"], g["bk"], g["G"]
    hb = kv_heads * G
    nq, nk = g["Sq"] // bq, g["Sk"] // bk
    common = dict(D=D, Dv=Dv, scale=D ** -0.5, bq=bq, bk=bk,
                  q_offset=g["q_offset"],
                  window=window, seq_k=g["seq_k"], group=G, fast=g["fast"],
                  padded=g["padded"], mxu_dtype=mxu_dtype or q.dtype)
    heads = g["KH"] // kv_heads

    qb = functools.partial(_q_block, bq=bq, bk=bk, q_offset=g["q_offset"],
                           q_steps=nq, window=window)
    q_spec = pl.BlockSpec((1, bq, hb * D),
                          lambda b, h, j, i: (b, qb(j, i), h))
    do_spec = pl.BlockSpec((1, bq, hb * Dv),
                           lambda b, h, j, i: (b, qb(j, i), h))
    row_spec = pl.BlockSpec((1, hb, 1, bq),
                            lambda b, h, j, i: (b, h, 0, qb(j, i)))
    kv_spec = pl.BlockSpec((1, bk, kv_heads * D),
                           lambda b, h, j, i: (b, j, h))
    v_spec = pl.BlockSpec((1, bk, kv_heads * Dv),
                          lambda b, h, j, i: (b, j, h))
    fuse_dq = nq == 1 and nk == 1
    outs = [jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [pltpu.VMEM((kv_heads, bk, D), jnp.float32),
               pltpu.VMEM((kv_heads, bk, Dv), jnp.float32)]
    if fuse_dq:
        outs.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch += [pltpu.VMEM((hb, D, bq), jnp.float32),
                    pltpu.VMEM((D, bk), common["mxu_dtype"])]
    config = (D, Dv, window, g["seq_k"], g["q_offset"], bq, bk, kv_heads,
              jnp.dtype(common["mxu_dtype"]).name, interpret)
    grads = _traced(
        "attn_bwd" if fuse_dq else "attn_dkv", config,
        lambda: pl.pallas_call(
            functools.partial(_dkv_kernel, q_steps=nq, fuse_dq=fuse_dq,
                              **common),
            grid=(B, heads, nk, nq),
            in_specs=[q_spec, kv_spec, v_spec, do_spec, row_spec, row_spec],
            out_specs=[kv_spec, v_spec] + [q_spec] * fuse_dq,
            out_shape=outs,
            scratch_shapes=scratch,
            interpret=interpret),
        q, k, v, do, lse, delta)
    if fuse_dq:
        dk, dv, dq = grads
        return dq, dk, dv
    dk, dv = grads

    kvb = functools.partial(_kv_block, bq=bq, bk=bk, q_offset=g["q_offset"],
                            window=window)
    q_spec = pl.BlockSpec((1, bq, hb * D), lambda b, h, i, j: (b, i, h))
    do_spec = pl.BlockSpec((1, bq, hb * Dv), lambda b, h, i, j: (b, i, h))
    row_spec = pl.BlockSpec((1, hb, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    kv_spec = pl.BlockSpec((1, bk, kv_heads * D),
                           lambda b, h, i, j: (b, kvb(i, j), h))
    v_spec = pl.BlockSpec((1, bk, kv_heads * Dv),
                          lambda b, h, i, j: (b, kvb(i, j), h))
    dq, = _traced(
        "attn_dq", config,
        lambda: pl.pallas_call(
            functools.partial(_dq_kernel, k_steps=nk, **common),
            grid=(B, heads, nq, nk),
            in_specs=[q_spec, kv_spec, v_spec, do_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32)],
            interpret=interpret),
        q, k, v, do, lse, delta)
    return dq, dk, dv
