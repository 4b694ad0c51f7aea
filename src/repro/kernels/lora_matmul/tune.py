"""Block-size selection for the fused LoRA kernels, memoized per process.

``best_blocks`` picks (bm, bn, bk) for one (M, K, N, r, dtype) problem
shape by one deterministic rule on every backend: drop the candidates
past the VMEM budget, then minimize padding waste.  The tiles a CPU
compile rehearsal lowers are therefore exactly the tiles the chip runs,
and the choice is made from shapes alone — the tuners are called while
the model's jitted step is being traced, where nothing can be timed.
The kernel is never launched with pathological tiles: a bk that blows
the VMEM budget, or 256-wide blocks wrapped around a 33-row ragged matmul
that would waste 7/8 of every MXU pass on padding.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp

Blocks = Tuple[int, int, int]
GatherBlocks = Tuple[int, int]

# key: (M, K, N, r, x dtype, WEIGHT dtype) — the weight dtype is part of
# the key because the int8 base variant has its own VMEM footprint and its
# own winner: an (int8 W, f32 scale) choice must never alias the
# f32-weight entry for the same logical shape
_CACHE: Dict[Tuple[int, int, int, int, str, str], Blocks] = {}
# the gathered (multi-tenant) variant memoizes SEPARATELY, and its key
# additionally covers the adapter-pool size and the index dtype: a
# single-adapter sweep and a multi-tenant sweep over the same (M, K, N, r)
# must never collide — the gather kernel's tiling trade-offs (bm == 1,
# per-row A/B DMA) are different from the dense kernel's
_GATHER_CACHE: Dict[Tuple[int, int, int, int, int, str, str],
                    GatherBlocks] = {}

_CANDIDATES: Tuple[Blocks, ...] = (
    (128, 128, 128), (128, 128, 256), (128, 256, 256), (256, 128, 256),
    (256, 256, 256), (256, 256, 512), (512, 256, 256), (128, 256, 512),
)
_GATHER_CANDIDATES: Tuple[GatherBlocks, ...] = (
    (128, 128), (128, 256), (256, 256), (256, 512), (512, 256), (128, 512),
)
_VMEM_BUDGET = 12 * 1024 * 1024        # leave headroom under ~16 MB/core


def clear_cache() -> None:
    _CACHE.clear()
    _GATHER_CACHE.clear()


def _vmem_bytes(bm: int, bn: int, bk: int, r: int, itemsize: int,
                w_itemsize: int | None = None) -> int:
    """Per-step VMEM footprint: double-buffered input tiles + f32 scratch."""
    w_itemsize = itemsize if w_itemsize is None else w_itemsize
    tiles = (itemsize * (bm * bk + r * bk + bn * r)
             + w_itemsize * bk * bn)
    scratch = 4 * (bm * bn + bm * r)
    out = itemsize * bm * bn
    return 2 * tiles + scratch + out


def _pad_up(d: int, b: int) -> int:
    return -(-d // b) * b


def _heuristic_key(M: int, K: int, N: int, c: Blocks):
    """Rank by padded-FLOP waste, then fewer K steps (fewer scratch
    round trips), then larger output tiles (MXU utilization)."""
    bm, bn, bk = c
    padded = _pad_up(M, bm) * _pad_up(K, bk) * _pad_up(N, bn)
    return (padded, _pad_up(K, bk) // bk, -(bm * bn))


def best_blocks(M: int, K: int, N: int, r: int, dtype=jnp.float32,
                w_dtype=None) -> Blocks:
    """Memoized (bm, bn, bk) for one fused-LoRA problem shape.

    ``w_dtype`` (default: same as ``dtype``) keys the weight-only
    quantized variant separately — an int8 base halves the W tile's VMEM
    and shifts the tiling optimum."""
    w_name = jnp.dtype(w_dtype if w_dtype is not None else dtype).name
    key = (int(M), int(K), int(N), int(r), jnp.dtype(dtype).name, w_name)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = jnp.dtype(dtype).itemsize
    w_itemsize = jnp.dtype(w_name).itemsize
    cands: List[Blocks] = []
    for bm, bn, bk in _CANDIDATES:
        c = (min(bm, M), min(bn, N), min(bk, K))
        if _vmem_bytes(*c, r=max(int(r), 1), itemsize=itemsize,
                       w_itemsize=w_itemsize) > _VMEM_BUDGET:
            continue
        if c not in cands:
            cands.append(c)
    if not cands:
        cands = [(min(128, M), min(128, N), min(128, K))]
    best = min(cands, key=lambda c: _heuristic_key(M, K, N, c))
    _CACHE[key] = best
    return best


# ---------------------------------------------------------------------------
# gathered (multi-tenant) variant
# ---------------------------------------------------------------------------

def _gather_vmem_bytes(bn: int, bk: int, r: int, itemsize: int) -> int:
    """Per-step VMEM of the gather kernel: bm == 1 row tiles, the row's
    gathered A/B tiles, and the (1, bn)/(1, r) f32 scratch."""
    tiles = itemsize * (bk + bk * bn + r * bk + bn * r)
    scratch = 4 * (bn + r)
    out = itemsize * bn
    return 2 * tiles + scratch + out


def _gather_heuristic_key(K: int, N: int, c: GatherBlocks):
    """Padded-FLOP waste over (K, N), then fewer K steps (fewer scratch
    round trips per output tile), then wider output tiles."""
    bn, bk = c
    padded = _pad_up(K, bk) * _pad_up(N, bn)
    return (padded, _pad_up(K, bk) // bk, -bn)


def best_gather_blocks(M: int, K: int, N: int, r: int, pool: int,
                       dtype=jnp.float32,
                       idx_dtype=jnp.int32) -> GatherBlocks:
    """Memoized (bn, bk) for one batched-gather LoRA problem shape."""
    key = (int(M), int(K), int(N), int(r), int(pool),
           jnp.dtype(dtype).name, jnp.dtype(idx_dtype).name)
    hit = _GATHER_CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = jnp.dtype(dtype).itemsize
    cands: List[GatherBlocks] = []
    for bn, bk in _GATHER_CANDIDATES:
        c = (min(bn, N), min(bk, K))
        if _gather_vmem_bytes(*c, r=max(int(r), 1),
                              itemsize=itemsize) > _VMEM_BUDGET:
            continue
        if c not in cands:
            cands.append(c)
    if not cands:
        cands = [(min(128, N), min(128, K))]
    best = min(cands, key=lambda c: _gather_heuristic_key(K, N, c))
    _GATHER_CACHE[key] = best
    return best
