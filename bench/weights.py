"""Weights made by the benchmark from the seed, on the device, in one jitted
call each, in the program's parameter layout (a GPT-2 block stacked over
depth) and in float32, the type they are run in.  The program and the
reference both take these; neither makes its own."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> tuple:
    """(layers, d_model, heads, d_ff, vocab, positions) of a config file."""
    return (cfg["n_layer"], cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
            cfg["vocab_size"], cfg["n_positions"])


@functools.partial(jax.jit, static_argnums=(0,))
def _params(shape: tuple, key):
    L, d, H, ff, V, P = shape
    ks = iter(jax.random.split(key, 24))
    nrm = lambda shp, std: std * jax.random.normal(next(ks), shp, jnp.float32)
    out_std = 0.02 / (2 * L) ** 0.5            # GPT-2's scaled residual init

    def norm():
        return {"scale": 1.0 + nrm((L, d), 0.1), "bias": nrm((L, d), 0.02)}

    def dense(d_in, d_out, std):
        return {"w": nrm((L, d_in, d_out), std), "b": nrm((L, d_out), 0.02)}

    layer = {
        "norm1": norm(),
        "mixer": {"wq": dense(d, d, 0.02), "wk": dense(d, d, 0.02),
                  "wv": dense(d, d, 0.02), "wo": dense(d, d, out_std)},
        "norm2": norm(),
        "mlp": {"w_up": dense(d, ff, 0.02), "w_down": dense(ff, d, out_std)},
    }
    return {"embed": {"tok": nrm((V, d), 0.02), "pos": nrm((P, d), 0.01)},
            "layers": (layer,),
            "final_norm": {"scale": 1.0 + nrm((d,), 0.1),
                           "bias": nrm((d,), 0.02)}}


def make_params(cfg: dict, key):
    return _params(dims(cfg), key)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _lora(n: int, L: int, d: int, r: int, key):
    ka, kb = jax.random.split(key)
    tgt = lambda k, i: {
        "a": r ** -0.5 * jax.random.normal(jax.random.fold_in(k, i),
                                           (n, L, r, d), jnp.float32),
        "b": jnp.zeros((n, L, d, r), jnp.float32)}
    return ({"mixer": {"q": tgt(ka, 0), "v": tgt(kb, 1)}},)


def make_loras(cfg: dict, n: int, key):
    """``n`` LoRA adapters on q and v of every layer, stacked on a leading
    axis, at the standard start of fine-tuning: A random, B = 0."""
    L, d = cfg["n_layer"], cfg["n_embd"]
    return _lora(n, L, d, cfg["lora_rank"], key)


def take(tree, i: int):
    return jax.tree.map(lambda v: v[i], tree)
