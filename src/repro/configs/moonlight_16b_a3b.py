"""Moonlight-16B-A3B — the DeepSeek-V3 block at its smallest published size:
multi-head latent attention without a query low-rank, one leading dense
SwiGLU layer, then 26 layers of 64 sigmoid-routed SwiGLU experts (top-6,
selection corrected by a per-expert bias, gate weights normalised and
scaled by 2.446) beside 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].

LoRA on the attention's ``q_proj`` (d -> heads x 192) and ``kv_b_proj``
(latent 512 -> heads x (128 k + 128 v)): the paper's q and v adapters on
the projections that make the query and, with the key, the value.
"""
from .base import ArchConfig, LayerPattern

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,                     # the leading dense layer
    vocab_size=163840,
    prefix=(LayerPattern(mixer="mla", mlp="dense"),),
    pattern=(LayerPattern(mixer="mla", mlp="moe"),),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    pos_emb="rope",
    mlp_kind="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=False,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    shared_expert=True,
    shared_d_ff=2 * 1408,           # n_shared_experts 2, as one SwiGLU
    moe_dispatch="dropless",        # sigmoid scores, noaux_tc bias
    routed_scale=2.446,
    router_aux_coef=0.0,            # the router is frozen: no balance term
    lora_targets=("q_proj", "kv_b_proj"),
    lora_rank=4,
    lora_alpha=8.0,
    max_seq_len=8192,
)
