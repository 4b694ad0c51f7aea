"""The plain reference: GPT-2 with LoRA on q and v, in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision.  It imports nothing
of the program and takes no weights the program made: the benchmark makes
the weights (``weights.py``) and hands the same ones to both.

``sfl_round`` is Algorithm 1's first global round as the paper states it:
each client runs embedding and layers [0, ell) with its own adapter, the
server runs layers [ell, L), the final norm and the tied LM head over the
pooled batch, the loss is the mean token cross-entropy, both sides take
one Adam step per local step, and after I local steps FedAvg replaces
every client adapter by their mean.  Computed client by client, so that
it fits beside nothing else on the chip.

``dtype`` bfloat16 selects the control: the same round computed one
precision step down, every array in bfloat16 (weights, activations,
logits and losses, the adapters, Adam's moments and FedAvg's mean).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(x, p, lo, heads, lscale, eps):
    """One pre-norm GPT-2 block; ``lo`` is this layer's {"q", "v"} adapter
    ({"a": (r, d), "b": (d, r)} each) or None."""
    B, S, d = x.shape
    hd = d // heads
    h = _ln(x, p["norm1"], eps)

    def proj(name, t):
        w = p["mixer"][name]
        y = h @ w["w"] + w["b"]
        if lo is not None and t in lo:
            y = y + lscale * ((h @ lo[t]["a"].T) @ lo[t]["b"].T)
        return y.reshape(B, S, heads, hd)

    q, k, v = proj("wq", "q"), proj("wk", "k"), proj("wv", "v")
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
    x = x + o @ p["mixer"]["wo"]["w"] + p["mixer"]["wo"]["b"]
    h = _ln(x, p["norm2"], eps)
    u = _gelu(h @ p["mlp"]["w_up"]["w"] + p["mlp"]["w_up"]["b"])
    return x + u @ p["mlp"]["w_down"]["w"] + p["mlp"]["w_down"]["b"]


def _layers(x, layers, lora, lo: int, hi: int, heads, lscale, eps):
    """Layers [lo, hi) of the stacked ``layers``; ``lora`` holds adapters
    for exactly those layers, in the program's layout: a one-pattern tuple
    of {"mixer": {"q", "v"}} with leading axis hi - lo."""
    sl = jax.tree.map(lambda v: v[lo:hi], layers)

    def body(x, xs):
        p, l = xs
        return _block(x, p, l, heads, lscale, eps), None

    x, _ = jax.lax.scan(body, x, (sl, lora[0]["mixer"]))
    return x


def _embed(params, tokens):
    S = tokens.shape[-1]
    return params["embed"]["tok"][tokens] + params["embed"]["pos"][:S]


def _head(params, x, eps):
    x = _ln(x, params["final_norm"], eps)
    return x @ params["embed"]["tok"].T


def _cast(tree, dtype):
    return jax.tree.map(lambda v: v.astype(dtype), tree)


# ---------------------------------------------------------------------------
# training: one SFL round
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _client_grads(heads, ell, L, lscale, eps, dtype, params, lora_c, lora_s,
                  tokens, labels, weight, n_total):
    """(CE sum over this client's labelled tokens / n_total, grads wrt the
    client's adapter and the server adapter).  ``weight`` is 1 for rows
    that count and 0 for rows left out."""
    params = _cast(params, dtype)

    def loss(lc, ls):
        x = _embed(params, tokens)
        x = _layers(x, params["layers"][0], lc, 0, ell, heads, lscale, eps)
        x = _layers(x, params["layers"][0], ls, ell, L, heads, lscale, eps)
        logits = _head(params, x, eps)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return jnp.sum(nll * weight.astype(dtype)[:, None]) / n_total

    return jax.value_and_grad(loss, argnums=(0, 1))(lora_c, lora_s)


def _adam(p, g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step over whole trees: (params, first moment, second)."""
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
    return p, m, v


def sfl_round(cfg: dict, params, lora_c, lora_s, tokens, labels, *, ell: int,
              lr: float, dtype=jnp.float32, half_batch: bool = False):
    """The first global round from adapters ``lora_c`` (leading axis K) and
    ``lora_s`` (layers [ell, L)).  tokens, labels: numpy (I, K, b, S).

    Returns host numpy trees: per-step losses (I,), the first Adam moment
    of both sides after the round, and both sides' adapters after FedAvg.
    ``half_batch`` is a planted fault: the second half of the pooled
    batch's rows leaves the loss, which is then the mean over the rest."""
    I, K, b, S = tokens.shape
    L, heads = cfg["n_layer"], cfg["n_head"]
    lscale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps = cfg["layer_norm_epsilon"]
    keep = np.ones((K, b), np.float32)
    if half_batch:
        keep.reshape(-1)[(K * b + 1) // 2:] = 0.0
    n_total = float(keep.sum() * S)
    lora_c, lora_s = _cast(lora_c, dtype), _cast(lora_s, dtype)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    mc, vc, ms, vs = zeros(lora_c), zeros(lora_c), zeros(lora_s), zeros(lora_s)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(I):
            gs = zeros(lora_s)
            gcs, loss = [], 0.0
            for k in range(K):
                lk, (gc, g) = _client_grads(
                    heads, ell, L, lscale, eps, dtype, params,
                    jax.tree.map(lambda v: v[k], lora_c), lora_s,
                    jnp.asarray(tokens[i, k]), jnp.asarray(labels[i, k]),
                    jnp.asarray(keep[k]), n_total)
                loss = loss + lk
                gcs.append(gc)
                gs = jax.tree.map(jnp.add, gs, g)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *gcs)
            t = float(i + 1)
            lora_c, mc, vc = _adam(lora_c, gc, mc, vc, t, lr)
            lora_s, ms, vs = _adam(lora_s, gs, ms, vs, t, lr)
            losses.append(float(loss))
    # FedAvg (equal sample counts): every client takes the clients' mean
    lora_c = jax.tree.map(
        lambda v: jnp.broadcast_to(v.mean(0, keepdims=True), v.shape), lora_c)
    host = lambda t: jax.tree.map(np.asarray, t)
    return {"losses": np.asarray(losses), "m_client": host(mc),
            "m_server": host(ms), "lora_client": host(lora_c),
            "lora_server": host(lora_s)}

