"""Fused LoRA matmul Pallas kernels: forward, dX, and rank reductions.

Forward: y = x W + scale * (x A^T) B^T.  The low-rank path rides in the
same (bm, bn) output tile as the base matmul — the extra arithmetic per
rank is exactly the paper's DeltaPhi(mu, r) term, and fusing it avoids a
second HBM pass over x.

Grid (M/bm, N/bn, K/bk), K innermost; VMEM scratch carries the f32 output
accumulator and the (bm, r) low-rank activation accumulator across K steps;
on the last K step the low-rank product is folded in and the tile is
written once.  MXU alignment: bm/bn/bk multiples of 128 (r is padded to the
lane width by Mosaic; r itself stays tiny — the paper's ranks are 1..8).

Backward (ops.py wires these into a custom VJP):

* ``lora_matmul_dx_kernel`` — dX = dY W^T + scale * (dY B) A, the mirror
  image of the forward: one tiled pass over W read in its native (K, N)
  layout (the contraction over N uses dot_general, no HBM transpose) with
  the rank-r correction accumulated in the same VMEM scratch scheme.
* ``lora_rank_reduce_kernel`` — out = u^T v for a rank-thin u, the shape
  of both adapter grads (dA = scale * (dY B)^T X, dB^T = scale *
  (X A^T)^T dY): the (r, bn) accumulator lives in VMEM across the M sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, y_ref, acc_ref, z_ref, *,
            scale: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    xb = x_ref[...]
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    # low-rank activation: z += x_tile @ A_tile^T   (bm, r)
    z_ref[...] += jnp.dot(xb, a_ref[...].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        y = acc_ref[...] + scale * jnp.dot(
            z_ref[...], b_ref[...].T, preferred_element_type=jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)


def lora_matmul_kernel(x, w, a, b, *, scale: float, bm: int = 256,
                       bn: int = 256, bk: int = 512,
                       interpret: bool = False):
    """x: (M, K); w: (K, N); a: (r, K); b: (N, r) — dims must divide by the
    block shape (ops.py pads)."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, N // bn, K // bk)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),     # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),     # w
            pl.BlockSpec((r, bk), lambda i, j, k: (0, k)),      # a
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),      # b
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
    )(x, w, a, b)


# ---------------------------------------------------------------------------
# weight-only int8 forward: W rides HBM as int8, dequantized per-tile in VMEM
# ---------------------------------------------------------------------------

def _q8_kernel(x_ref, w_ref, ws_ref, a_ref, b_ref, y_ref, acc_ref, z_ref, *,
               scale: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    xb = x_ref[...].astype(jnp.float32)
    # per-output-channel dequant in VMEM: the int8 tile costs half the HBM
    # bytes of bf16 and a quarter of f32 — the multiply is VPU noise next
    # to the MXU dot it feeds
    wf = w_ref[...].astype(jnp.float32) * ws_ref[...]
    acc_ref[...] += jnp.dot(xb, wf, preferred_element_type=jnp.float32)
    z_ref[...] += jnp.dot(xb, a_ref[...].astype(jnp.float32).T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        y = acc_ref[...] + scale * jnp.dot(
            z_ref[...], b_ref[...].astype(jnp.float32).T,
            preferred_element_type=jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)


def lora_matmul_q8_kernel(x, w_q, w_scale, a, b, *, scale: float,
                          bm: int = 256, bn: int = 256, bk: int = 512,
                          interpret: bool = False):
    """Forward fused LoRA matmul over an ``(int8 W, f32 scale)`` base.

    x: (M, K); w_q: int8 (K, N); w_scale: f32 (1, N) per-output-channel;
    a: (r, K); b: (N, r) — dims must divide by the block shape (ops.py
    pads).  Same tiling as ``lora_matmul_kernel`` plus one (1, bn) scale
    tile per N block.
    """
    M, K = x.shape
    N = w_q.shape[1]
    r = a.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, N // bn, K // bk)

    return pl.pallas_call(
        functools.partial(_q8_kernel, scale=scale, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),     # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),     # w_q
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),      # w_scale
            pl.BlockSpec((r, bk), lambda i, j, k: (0, k)),      # a
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),      # b
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
    )(x, w_q, w_scale, a, b)


def _q8_dx_kernel(dy_ref, w_ref, ws_ref, a_ref, b_ref, dx_ref, acc_ref,
                  z_ref, *, scale: float, n_steps: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    dyb = dy_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32) * ws_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        dyb, wf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    z_ref[...] += jnp.dot(dyb, b_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    @pl.when(n == n_steps - 1)
    def _finish():
        dx = acc_ref[...] + scale * jnp.dot(
            z_ref[...], a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        dx_ref[...] = dx.astype(dx_ref.dtype)


def lora_matmul_q8_dx_kernel(dy, w_q, w_scale, a, b, *, scale: float,
                             bm: int = 256, bn: int = 256, bk: int = 512,
                             interpret: bool = False):
    """dX = dY @ (W_q * scale)^T + scale_lora * (dY @ B) @ A.

    dy: (M, N); w_q: int8 (K, N) forward layout; w_scale: f32 (1, N);
    a: (r, K); b: (N, r) — dims must divide by the block shape.  Mirrors
    ``lora_matmul_dx_kernel`` with the per-tile dequant of the q8 forward.
    """
    M, N = dy.shape
    K = w_q.shape[0]
    r = a.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, K // bk, N // bn)

    return pl.pallas_call(
        functools.partial(_q8_dx_kernel, scale=scale, n_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),     # dy
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),     # w_q
            pl.BlockSpec((1, bn), lambda i, j, n: (0, n)),      # w_scale
            pl.BlockSpec((r, bk), lambda i, j, n: (0, j)),      # a
            pl.BlockSpec((bn, r), lambda i, j, n: (n, 0)),      # b
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), dy.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
    )(dy, w_q, w_scale, a, b)


# ---------------------------------------------------------------------------
# batched-gather forward (multi-tenant serving)
# ---------------------------------------------------------------------------

def _gather_kernel(idx_ref, x_ref, w_ref, a_ref, b_ref, y_ref, acc_ref,
                   z_ref, *, scale: float, k_steps: int):
    del idx_ref          # consumed by the BlockSpec index maps, not the body
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    xb = x_ref[0]                                         # (1, bk)
    acc_ref[...] += jnp.dot(xb, w_ref[...],
                            preferred_element_type=jnp.float32)
    # this row's OWN adapter tile: the prefetched index map already DMA'd
    # A[idx[m]] — the body is identical to the single-adapter kernel
    z_ref[...] += jnp.dot(xb, a_ref[0].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _finish():
        y = acc_ref[...] + scale * jnp.dot(
            z_ref[...], b_ref[0].T, preferred_element_type=jnp.float32)
        y_ref[0] = y.astype(y_ref.dtype)


def lora_matmul_gather_kernel(x, w, a_pool, b_pool, idx, *, scale: float,
                              bn: int = 256, bk: int = 512,
                              interpret: bool = False):
    """Punica/S-LoRA-style batched-gather LoRA matmul.

    x: (M, K) — one row per serving slot; w: (K, N); a_pool: (A, r, K) and
    b_pool: (A, N, r) — ALL resident tenant adapters stacked on a leading
    pool axis; idx: (M,) int32 adapter index per row.

    ``idx`` rides in as a scalar-prefetch operand
    (``pltpu.PrefetchScalarGridSpec``) so the A/B BlockSpec index maps can
    compute each row's physical DMA source — ``(idx[m], 0, k)`` /
    ``(idx[m], j, 0)`` — before the body runs: the gather IS the index
    map, exactly the block-table trick in ``flash_attention/paged_decode``.
    A mixed-tenant batch therefore decodes in ONE kernel call with no
    host-side regrouping and no materialized per-row adapter copy.

    Grid (M, N/bn, K/bk): one grid row per slot (decode batches are
    slot-count sized, so bm == 1 costs nothing and lets neighbouring rows
    wear different adapters).  N and K must divide by the block shape
    (ops.py pads).  x and y are viewed as (M, 1, K) / (M, 1, N) so each
    row's (1, 1, bk) block spans the full second-minor dimension — Mosaic
    refuses a (1, bk) block of an (M, K) array unless M == 1.
    """
    M, K = x.shape
    N = w.shape[1]
    r = a_pool.shape[1]
    bn, bk = min(bn, N), min(bk, K)
    grid = (M, N // bn, K // bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda m, j, k, idx: (m, 0, k)),   # x
            pl.BlockSpec((bk, bn), lambda m, j, k, idx: (k, j)),        # w
            pl.BlockSpec((1, r, bk),
                         lambda m, j, k, idx: (idx[m], 0, k)),          # A
            pl.BlockSpec((1, bn, r),
                         lambda m, j, k, idx: (idx[m], j, 0)),          # B
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda m, j, k, idx: (m, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32),
                        pltpu.VMEM((1, r), jnp.float32)],
    )
    y = pl.pallas_call(
        functools.partial(_gather_kernel, scale=scale, k_steps=grid[2]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, 1, N), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x.reshape(M, 1, K), w, a_pool, b_pool)
    return y.reshape(M, N)


# ---------------------------------------------------------------------------
# backward: dX
# ---------------------------------------------------------------------------

def _dx_kernel(dy_ref, w_ref, a_ref, b_ref, dx_ref, acc_ref, z_ref, *,
               scale: float, n_steps: int):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    dyb = dy_ref[...]
    # dY_tile (bm, bn) contracted with W_tile (bk, bn) over the shared N
    # blocks — W stays in its forward (K, N) layout, no HBM transpose.
    acc_ref[...] += jax.lax.dot_general(
        dyb, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # low-rank grad activation: z += dY_tile @ B_tile   (bm, r)
    z_ref[...] += jnp.dot(dyb, b_ref[...],
                          preferred_element_type=jnp.float32)

    @pl.when(n == n_steps - 1)
    def _finish():
        dx = acc_ref[...] + scale * jnp.dot(
            z_ref[...], a_ref[...], preferred_element_type=jnp.float32)
        dx_ref[...] = dx.astype(dx_ref.dtype)


def lora_matmul_dx_kernel(dy, w, a, b, *, scale: float, bm: int = 256,
                          bn: int = 256, bk: int = 512,
                          interpret: bool = False):
    """dX = dY @ W^T + scale * (dY @ B) @ A.

    dy: (M, N); w: (K, N)-layout base weight (i.e. forward layout); a:
    (r, K); b: (N, r) — dims must divide by the block shape (ops.py pads).
    """
    M, N = dy.shape
    K = w.shape[0]
    r = a.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    grid = (M // bm, K // bk, N // bn)

    return pl.pallas_call(
        functools.partial(_dx_kernel, scale=scale, n_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),     # dy
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),     # w
            pl.BlockSpec((r, bk), lambda i, j, n: (0, j)),      # a
            pl.BlockSpec((bn, r), lambda i, j, n: (n, 0)),      # b
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), dy.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
    )(dy, w, a, b)


# ---------------------------------------------------------------------------
# backward: dA / dB rank reductions
# ---------------------------------------------------------------------------

def _rank_reduce_kernel(u_ref, v_ref, o_ref, acc_ref, *, m_steps: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # operands stream from HBM in their native dtype; the upcast happens
    # per-tile in VMEM so the adapter grad is f32-exact at no HBM cost
    acc_ref[...] += jax.lax.dot_general(
        u_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == m_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def lora_rank_reduce_kernel(u, v, *, bm: int = 256, bn: int = 256,
                            interpret: bool = False):
    """out = u^T @ v — the adapter-grad reduction.

    u: (M, r) rank-thin; v: (M, N).  Returns (r, N) f32: the (r, bn)
    accumulator stays in VMEM scratch across the whole M sweep, so the
    rank-sized grad is written to HBM exactly once per N tile.
    """
    M, r = u.shape
    N = v.shape[1]
    bm, bn = min(bm, M), min(bn, N)
    grid = (N // bn, M // bm)

    return pl.pallas_call(
        functools.partial(_rank_reduce_kernel, m_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r), lambda i, j: (j, 0)),         # u
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),        # v
        ],
        out_specs=pl.BlockSpec((r, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32)],
        interpret=interpret,
    )(u, v)
