"""Cache-length block selection for the flash-decode kernels, memoized per
process — the ``lora_matmul/tune.py`` rule applied to split-K decode.

``best_decode_block`` picks the kv-tile size ``bk`` for one
(B, KH, G, L, D, dtype) decode problem by one deterministic waste rule on
every backend (the tuners run while the decode step is traced, where
nothing can be timed): a big bk wastes MXU work on the partially-live
last tile of every slot (the steady-state live length is unknown at trace
time, so the rule scores the expected half-full tile), a tiny bk pays
more grid steps and scratch round-trips.  The kernel never launches with
a pathological tile — a bk past the VMEM budget or wider than the cache.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

# key: (B, KH, G, L, D, q dtype, KV dtype) — the kv dtype keys the int8-KV
# variant separately: its tiles cost a quarter of the f32 VMEM, so the
# winning bk differs from the same logical shape in f32
_CACHE: Dict[Tuple[int, int, int, int, int, str, str], int] = {}

_CANDIDATES: Tuple[int, ...] = (128, 256, 512, 1024)
_VMEM_BUDGET = 12 * 1024 * 1024        # leave headroom under ~16 MB/core


def clear_cache() -> None:
    _CACHE.clear()


def _vmem_bytes(bk: int, G: int, D: int, itemsize: int,
                kv_itemsize: int | None = None) -> int:
    """Per-step VMEM: double-buffered k/v tiles + q + f32 scratch + out."""
    kv_itemsize = itemsize if kv_itemsize is None else kv_itemsize
    tiles = kv_itemsize * 2 * bk * D + itemsize * G * D
    scratch = 4 * (2 * G * 128 + G * D)
    return 2 * tiles + scratch + itemsize * G * D


def _heuristic_key(L: int, bk: int):
    """Expected wasted lanes on the half-full boundary tile, then fewer
    grid steps (scratch round-trips) as the tie-break."""
    steps = -(-L // bk)
    return (bk // 2 + (-L) % bk, steps)


def best_decode_block(B: int, KH: int, G: int, L: int, D: int,
                      dtype=jnp.float32, kv_dtype=None) -> int:
    """Memoized ``bk`` for one flash-decode problem shape.

    ``kv_dtype`` (default: same as ``dtype``) keys the int8-KV variant
    separately — smaller kv tiles admit larger candidates."""
    kv_name = jnp.dtype(kv_dtype if kv_dtype is not None else dtype).name
    key = (int(B), int(KH), int(G), int(L), int(D),
           jnp.dtype(dtype).name, kv_name)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = jnp.dtype(dtype).itemsize
    kv_itemsize = jnp.dtype(kv_name).itemsize
    cands = [min(bk, L) for bk in _CANDIDATES
             if _vmem_bytes(min(bk, L), max(G, 1), D, itemsize,
                            kv_itemsize=kv_itemsize) <= _VMEM_BUDGET]
    cands = sorted(set(cands)) or [min(128, L)]
    best = min(cands, key=lambda bk: _heuristic_key(L, bk))
    _CACHE[key] = best
    return best


# -- paged decode: the kv tile must divide the page size --------------------

# key additionally carries the KV-pool dtype (int8 pools key separately)
_PAGED_CACHE: Dict[Tuple[int, int, int, int, int, int, str, str], int] = {}


def clear_paged_cache() -> None:
    _PAGED_CACHE.clear()


def best_paged_block(B: int, KH: int, G: int, MP: int, PS: int, D: int,
                     dtype=jnp.float32, kv_dtype=None) -> int:
    """Memoized kv-tile size for one paged-decode problem — the
    ``(page_size, bk)`` twin of ``best_decode_block``.  Candidates are the
    divisors of ``page_size`` within the VMEM budget (a paged tile can
    never span two pages: they are not adjacent in the pool), and the
    largest wins — paged tiles are fully live up to the length boundary,
    so fewer grid steps is the whole game."""
    kv_name = jnp.dtype(kv_dtype if kv_dtype is not None else dtype).name
    key = (int(B), int(KH), int(G), int(MP), int(PS), int(D),
           jnp.dtype(dtype).name, kv_name)
    hit = _PAGED_CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = jnp.dtype(dtype).itemsize
    kv_itemsize = jnp.dtype(kv_name).itemsize
    cands = [bk for bk in set(_CANDIDATES) | {PS}
             if bk <= PS and PS % bk == 0
             and _vmem_bytes(bk, max(G, 1), D, itemsize,
                             kv_itemsize=kv_itemsize) <= _VMEM_BUDGET]
    cands = sorted(cands) or [PS]
    best = cands[-1]
    _PAGED_CACHE[key] = best
    return best
