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

``best_train_blocks`` picks the training kernels' (bq, bk, KV heads per
grid step) by the same kind of rule: VMEM budget, padding, the smallest
legal head group, then grid steps.
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


# -- training attention: (bq, bk, kv heads per grid step) -------------------

# key: (B, H, KH, Sq, Sk, D, dtype, Dv)
_TRAIN_CACHE: Dict[Tuple[int, int, int, int, int, int, str, int],
                   Tuple[int, int, int]] = {}

_TRAIN_CANDIDATES: Tuple[int, ...] = (128, 256, 512)


def clear_train_cache() -> None:
    _TRAIN_CACHE.clear()


def _seq_blocks(S: int, sublanes: int) -> Tuple[int, ...]:
    """A sequence at or under one lane tile is one whole block (rounded to
    the sublane tile); longer ones take 128-multiples, so the row-form
    log-sum-exp block stays lane-aligned."""
    if S <= _TRAIN_CANDIDATES[0]:
        return (-(-S // sublanes) * sublanes,)
    return _TRAIN_CANDIDATES


def _train_vmem_bytes(bq: int, bk: int, kv: int, G: int, D: int,
                      itemsize: int, Dv: int | None = None) -> int:
    """Per-step VMEM of the largest of the training kernels: its
    double-buffered tiles (the merged-head lanes rounded up to 128) and
    row statistics, its f32 scratch (the one-block backward's dQ^T and
    k^T included), and the sub-tiles of one head's work (about eight live
    128 x 128 f32 tiles).  ``Dv``: v's head width where it is not D (o,
    dO and dV take it)."""
    Dv = D if Dv is None else Dv
    r = lambda n: -(-n // 128) * 128
    hb = kv * G
    ql, kl, dp = r(hb * D), r(kv * D), r(D)
    ol, vl = r(hb * Dv), r(kv * Dv)
    rows = 2 * hb * 4 * 8 * bq                     # lse and delta blocks
    fwd = (2 * itemsize * (bq * (ql + ol) + bk * (kl + vl)) + rows
           + 4 * hb * (2 * 8 * bq + Dv * bq) + 4 * kv * Dv * bk)
    bwd = (2 * (itemsize * (bq * (2 * ql + ol) + 2 * bk * (kl + vl)) + rows)
           + 4 * (kv * bk * (dp + r(Dv)) + hb * D * bq + D * bk))
    dq = (2 * (itemsize * (bq * (2 * ql + ol) + bk * (kl + vl)) + rows)
          + 4 * hb * bq * dp)
    return max(fwd, bwd, dq) + 8 * 4 * 128 * 128


def _lane_ok(heads: int, total: int, D: int) -> bool:
    """A block of ``heads`` merged heads is a legal lane block: a multiple
    of 128 lanes, or all of them."""
    return heads == total or (heads * D) % 128 == 0


def best_train_blocks(B: int, H: int, KH: int, Sq: int, Sk: int, D: int,
                      dtype=jnp.float32,
                      Dv: int | None = None) -> Tuple[int, int, int]:
    """Memoized (bq, bk, kv_heads) for the training attention kernels.

    The decode rule's shape: drop what is past the VMEM budget, then the
    least padding, then the smallest legal head group (the kernels unroll
    their loops over heads and sub-tiles, so every head in a step adds
    trace and compile time to each start, while a grid step costs about
    a third of a microsecond), then the fewest grid steps.  Self-attention
    (Sq == Sk) takes square blocks, which the kernels' causal fast path
    needs; a head group must be a legal lane block of the merged-head
    layout, for q and k's width D and v's width ``Dv`` (default D)."""
    Dv = D if Dv is None else int(Dv)
    key = (int(B), int(H), int(KH), int(Sq), int(Sk), int(D),
           jnp.dtype(dtype).name, Dv)
    hit = _TRAIN_CACHE.get(key)
    if hit is not None:
        return hit
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    G = H // KH
    pad = lambda S, b: -(-S // b) * b
    kvs = [kv for kv in range(1, KH + 1) if KH % kv == 0
           and all(_lane_ok(kv, KH, w) and _lane_ok(kv * G, H, w)
                   for w in (D, Dv))]
    cands = [(bq, bk, kv)
             for bq in _seq_blocks(Sq, sub) for bk in _seq_blocks(Sk, sub)
             if Sq != Sk or bq == bk
             for kv in kvs
             if _train_vmem_bytes(bq, bk, kv, G, D, itemsize,
                                  Dv) <= _VMEM_BUDGET]
    if not cands:
        cands = [(_seq_blocks(Sq, sub)[0], _seq_blocks(Sk, sub)[0], kvs[0])]
    best = min(cands, key=lambda c: (pad(Sq, c[0]) + pad(Sk, c[1]), c[2],
                                     (pad(Sq, c[0]) // c[0])
                                     * (pad(Sk, c[1]) // c[1])))
    _TRAIN_CACHE[key] = best
    return best
