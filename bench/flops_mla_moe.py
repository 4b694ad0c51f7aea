"""Operations and bytes from shapes for the DeepSeek-V3 block (latent
attention, a leading dense SwiGLU layer, MoE layers of held routed
experts beside shared ones), LoRA on q_proj and kv_b_proj, base frozen.

Keys are those of DeepSeek-V3's ``config.json`` (``bench/configs/
moonlight-ep8.json``).  A matmul of an (m, k) by a (k, n) operand is 2mkn
operations.  Causal attention over a context of c tokens costs each
query and head 2 c (qk width) operations for q k^T and 2 c (v width) for
p v.

``train_flops_per_token`` counts what the algorithm needs (no recompute,
no padding, no rows that reach no held expert): MFU's numerator.  The
kernel counts are of one call each, as the program makes the calls: a
backward kernel that makes a product of the forward again does that work
too, and its roofline counts it."""
from __future__ import annotations


def _widths(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"d": cfg["hidden_size"], "H": H, "dqk": dn + dr, "dn": dn,
            "dv": dv, "R": cfg["kv_lora_rank"], "r": cfg["lora_rank"],
            "ff": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["deployment"]["router_experts"],
            "V": cfg["vocab_size"]}


def _proj(w: dict) -> dict:
    """Per token, the attention's frozen projections by name."""
    d, H, dqk, dn, dv, R = (w[k] for k in ("d", "H", "dqk", "dn", "dv", "R"))
    return {"q_proj": 2 * d * H * dqk, "kv_a_proj": 2 * d * (R + dqk - dn),
            "kv_b_proj": 2 * R * H * (dn + dv), "o_proj": 2 * H * dv * d}


def train_flops_per_token(cfg: dict, seq: int, rows_per_token: float) -> float:
    """Forward plus LoRA's backward for one token of an S-token sequence;
    ``rows_per_token``: (token, choice) rows that reach a held expert, per
    token and MoE layer (the counter's, or top-k x held / router experts).

    Backward: activation gradients through the frozen weights of every
    layer down to the lowest adapted projections (layer 0's q_proj and
    kv_b_proj), the adapter gradients, no frozen-weight gradients.  Layer
    0 needs no input gradient through q_proj, kv_a_proj and kv_b_proj and
    its keys' rotary dims none; every other layer needs them all, the
    router's included (the gate weights depend on the input)."""
    w = _widths(cfg)
    d, H, dqk, dn, dv, r = (w[k] for k in ("d", "H", "dqk", "dn", "dv", "r"))
    L = cfg["num_hidden_layers"]
    F = cfg["first_k_dense_replace"]
    ctx = (seq + 1) / 2                          # mean causal context
    proj = _proj(w)
    attn_f = 2 * H * ctx * (dqk + dv)            # q k^T and p v
    dense_mlp = 3 * 2 * d * w["ff"]
    moe_mlp = (2 * d * w["E"] + 3 * 2 * d * w["fs"]
               + rows_per_token * 3 * 2 * d * w["fe"])
    lora_io = ((d, H * dqk), (w["R"], H * (dn + dv)))  # q_proj, kv_b_proj
    lora_f = sum(2 * r * (i + o) for i, o in lora_io)
    mm = sum(proj.values())
    fwd = (L * (mm + attn_f + lora_f) + F * dense_mlp + (L - F) * moe_mlp
           + 2 * d * w["V"])
    # dX through every matmul, attention's dP, dV, dQ, dK, and the head's
    bwd = (L * (mm + 2 * attn_f) + F * dense_mlp + (L - F) * moe_mlp
           + 2 * d * w["V"])
    # layer 0: no dX via q_proj, kv_a_proj, kv_b_proj; dK of the plain
    # dims only
    bwd -= proj["q_proj"] + proj["kv_a_proj"] + proj["kv_b_proj"]
    bwd -= 2 * H * ctx * (dqk - dn)
    # per adapted projection: z2 = dY b, dA = z2^T x, dB = dY^T z (z kept
    # from the forward), and dX's low-rank part z2 a where dX is needed
    # (not in layer 0)
    bwd += L * sum(2 * r * (2 * o + i) for i, o in lora_io)
    bwd += (L - 1) * sum(2 * r * i for i, _ in lora_io)
    return float(fwd + bwd)


# ---------------------------------------------------------------------------
# kernels: one call's operations and HBM bytes
# ---------------------------------------------------------------------------

def gmm(rows: int, k: int, n: int, experts: int, item_in: int = 2,
        item_out: int = 4) -> tuple:
    """One grouped matmul over the held experts (``kernels/grouped_matmul``):
    ``rows`` rows of width k, each by its expert's (k, n) matrix; operands
    in bfloat16, the f32 result."""
    ops = 2 * rows * k * n
    byt = item_in * (rows * k + experts * k * n) + item_out * rows * n
    return float(ops), float(byt)


def expert_swiglu_calls(rows: int, cfg: dict) -> dict:
    """The grouped matmuls of one MoE layer's held experts in one local
    step over ``rows`` routed rows, as (ops, bytes) by kernel scope: in
    ``moe_gmm_fwd`` the forward's gate, up and down products and the
    backward's three made again from the kept rows; in ``moe_gmm_dx`` the
    dX through down, gate and up (frozen weights: no dW)."""
    d, f, E = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["n_routed_experts"]
    up, down = gmm(rows, d, f, E), gmm(rows, f, d, E)
    return {"moe_gmm_fwd": [up, up, down] * 2,
            "moe_gmm_dx": [gmm(rows, d, f, E), down, down]}


def attention_calls(batch: int, seq: int, cfg: dict, item: int = 4) -> tuple:
    """(forward, backward) of the training attention kernels over
    ``batch`` sequences: (ops, bytes) each.  The forward makes q k^T and
    p v, reads q, k, v and writes o and the log-sum-exp; the backward
    makes q k^T again, dP, dV, dQ and dK (the least any backward kernel
    does: split into a dK/dV and a dQ kernel it makes q k^T and dP twice),
    reads q, k, v, o, dO and the log-sum-exp and writes dQ, dK, dV."""
    H = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = batch * H * seq * (seq + 1) / 2      # causal (query, key) pairs
    qkv = batch * seq * H * (2 * dqk + dv)
    o = batch * seq * H * dv
    lse = batch * seq * H
    fwd = (2 * pairs * (dqk + dv), item * (qkv + o) + 4 * lse)
    bwd = (2 * pairs * (3 * dqk + 2 * dv),
           item * (2 * qkv + 2 * o) + 4 * lse)
    return fwd, bwd


def roofline_s(ops: float, byt: float, chip: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / chip["bf16_flops_per_s"], byt / chip["hbm_bytes_per_s"])
