"""Weights made by the benchmark from the seed for the DeepSeek-V3 block
(``bench/configs/moonlight-ep8.json``), on the device, in one jitted call
each, in float32 and in the program's parameter layout: the leading dense
layers stacked over depth under "prefix", the MoE layers under "layers",
an untied embedding and head.  The program and the reference both take
these; neither makes its own.

Projections are normal with std fan_in^-1/2; norm scales 1 + N(0, 0.1);
the router's correction bias N(0, 0.02), drawn like every other weight
and not tuned to balance the experts."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> tuple:
    """The shapes a config file fixes, as one hashable tuple."""
    F = cfg["first_k_dense_replace"]
    return (F, cfg["num_hidden_layers"] - F, cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["deployment"]["router_experts"],
            cfg["vocab_size"])


@functools.partial(jax.jit, static_argnums=(0,))
def _params(shape: tuple, key):
    F, L, d, H, dn, dr, dv, R, ff, fe, fs, E, N, V = shape
    keys = iter(jax.random.split(key, 40))
    nrm = lambda shp, std: std * jax.random.normal(next(keys), shp,
                                                   jnp.float32)
    w = lambda n, *shp: {"w": nrm((n,) + shp, shp[0] ** -0.5)}
    scale = lambda n, width: {"scale": 1.0 + nrm((n, width), 0.1)}

    def mixer(n):
        return {"q_proj": w(n, d, H * (dn + dr)), "kv_a_proj": w(n, d, R + dr),
                "kv_norm": scale(n, R), "kv_b_proj": w(n, R, H * (dn + dv)),
                "o_proj": w(n, H * dv, d)}

    def swiglu(n, f):
        return {"w_gate": w(n, d, f), "w_up": w(n, d, f),
                "w_down": w(n, f, d)}

    prefix = {"norm1": scale(F, d), "mixer": mixer(F), "norm2": scale(F, d),
              "mlp": swiglu(F, ff)}
    moe = {"router": {"w": nrm((L, d, N), d ** -0.5),
                      "bias": nrm((L, N), 0.02)},
           "shared": swiglu(L, fs),
           "w_gate": nrm((L, E, d, fe), d ** -0.5),
           "w_up": nrm((L, E, d, fe), d ** -0.5),
           "w_down": nrm((L, E, fe, d), fe ** -0.5)}
    layers = {"norm1": scale(L, d), "mixer": mixer(L), "norm2": scale(L, d),
              "mlp": moe}
    return {"embed": {"tok": nrm((V, d), 1.0), "unembed": nrm((d, V),
                                                            d ** -0.5)},
            "prefix": (prefix,), "layers": (layers,),
            "final_norm": {"scale": 1.0 + nrm((d,), 0.1)}}


def make_params(cfg: dict, key):
    return _params(dims(cfg), key)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _lora(shape: tuple, r: int, key):
    F, L, d, H, dn, dr, dv, R = shape[:8]

    def tgt(k, n, d_in, d_out):
        return {"a": r ** -0.5 * jax.random.normal(k, (n, r, d_in),
                                                   jnp.float32),
                "b": jnp.zeros((n, d_out, r), jnp.float32)}

    def mixer(k, n):
        kq, kv = jax.random.split(k)
        return {"mixer": {"q_proj": tgt(kq, n, d, H * (dn + dr)),
                          "kv_b_proj": tgt(kv, n, R, H * (dn + dv))}}

    kp, kl = jax.random.split(key)
    return {"prefix": (mixer(kp, F),), "layers": (mixer(kl, L),)}


def make_lora(cfg: dict, key):
    """LoRA adapters on q_proj and kv_b_proj of every layer at the standard
    start of fine-tuning: A random, B = 0."""
    return _lora(dims(cfg), cfg["lora_rank"], key)
