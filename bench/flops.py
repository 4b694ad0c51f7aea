"""Operations and bytes from shapes: the work the algorithm needs, not the
work the compiler emitted (no recompute, no padding).

GPT-2 blocks with LoRA on q and v, base weights frozen.  A matmul of an
(m, k) by a (k, n) operand is 2mkn operations.  Causal attention over a
context of c tokens costs each query 2 d c operations for q k^T and as
many for p v."""
from __future__ import annotations


def _block_matmul(d: int, ff: int) -> int:
    """Per token and layer: q, k, v, o and the two MLP projections."""
    return 2 * (4 * d * d + 2 * d * ff)


def _lora(d: int, r: int) -> int:
    """Per token and adapted projection: x a^T then z b^T."""
    return 2 * r * (d + d)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus LoRA's backward for one token of an S-token sequence.

    Backward: activation gradients through the frozen weights of every
    layer down to the lowest adapted projections (layer 0's q and v), the
    adapter gradients, no frozen-weight gradients.  Layer 0 needs no input
    gradient through q, k, v and no gradient into its keys."""
    L, d, ff, V, r = (cfg["n_layer"], cfg["n_embd"], cfg["n_inner"],
                      cfg["vocab_size"], cfg["lora_rank"])
    mm = _block_matmul(d, ff)
    ctx = (seq + 1) / 2                      # mean causal context
    attn_f = 4 * d * ctx                     # q k^T and p v
    lora_f = 2 * _lora(d, r)                 # q and v
    fwd = L * (mm + attn_f + lora_f) + 2 * d * V
    # dX through every matmul, dP, dV, dQ, dK, and the head's dX
    bwd = L * (mm + 2 * attn_f) + 2 * d * V
    bwd -= 2 * 3 * d * d + d * 2 * ctx       # layer 0: no dX via q,k,v; no dK
    # per adapted projection: z2 = dY b, dA = z2^T x, dB = dY^T z (z kept
    # from the forward), and dX's low-rank part z2 a where dX is needed
    # (not in layer 0)
    bwd += L * 2 * (3 * 2 * r * d) + (L - 1) * 2 * (2 * r * d)
    return float(fwd + bwd)


# ---------------------------------------------------------------------------
# kernels (kernels/lora_matmul): one call's operations and HBM bytes
# ---------------------------------------------------------------------------

def lora_fwd(m: int, k: int, n: int, r: int, item: int = 4) -> tuple:
    """y = x w + s (x a^T) b^T: x (m, k), w (k, n), a (r, k), b (n, r)."""
    ops = 2 * m * k * n + 2 * m * r * (k + n)
    byt = item * (m * k + k * n + r * (k + n) + m * n)
    return float(ops), float(byt)


def lora_dx(m: int, k: int, n: int, r: int, item: int = 4) -> tuple:
    """dx = dy w^T + s (dy b) a: the mirror image of the forward."""
    return lora_fwd(m, n, k, r, item)


def rank_reduce(m: int, r: int, n: int, item: int = 4) -> tuple:
    """u^T v for u (m, r), v (m, n): an adapter gradient."""
    return float(2 * m * r * n), float(item * (m * r + m * n + r * n))


def roofline_s(ops: float, byt: float, chip: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / chip["bf16_flops_per_s"], byt / chip["hbm_bytes_per_s"])
