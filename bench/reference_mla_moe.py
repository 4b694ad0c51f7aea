"""The plain reference of a DeepSeek-V3 block model (Moonlight-16B-A3B and
its kin) with LoRA on q_proj and kv_b_proj, in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision.  It imports
nothing of the program and makes no weights: the caller hands the same
ones to both, in the program's parameter layout (the leading dense layers
under "prefix", the MoE layers stacked over depth under "layers", untied
embedding and head).

``c``, the configuration, uses the key names of DeepSeek-V3's
``config.json`` with the values of the cut that runs: ``n_routed_experts``
is the number of experts held, and ``deployment`` gives ``first_expert``
(the id of the first one held) and ``router_experts`` (how many the
router scores); plus ``lora_rank`` and ``lora_alpha``.

The block, as DeepSeek-V3's modeling code states it (pre-norm RMSNorm,
eps ``rms_norm_eps``):

* latent attention with no query low-rank: q = x Wq (heads x (plain +
  rotary)); [c, k_r] = x Wkv_a; c = RMSNorm(c) (eps 1e-6, the module's
  default); [k_plain, v] = c Wkv_b per head; the rotary dims of q and the
  one shared k_r turn by position as adjacent pairs (theta
  ``rope_theta``, no scaling); causal softmax over
  q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim); o = (p v) Wo.
* the leading ``first_k_dense_replace`` layers: a SwiGLU MLP of width
  ``intermediate_size``.
* the MoE layers: scores = sigmoid(x Wg) over all router experts; the
  top-k of scores + correction bias choose; the chosen scores, normalised
  to sum 1, times ``routed_scaling_factor``, weigh the experts; plus the
  shared experts as one SwiGLU of n_shared x moe_intermediate_size.

Departures from the published model, each a cut of the configuration:
only the held experts' part of the mixture is computed (each held expert
runs on every token and is masked by its combine weight: no sort, no
grouped matmul), and the absent experts' part is left out, as in the
program; the vocabulary and the depth are the configuration's.

``sfl_round`` replays Algorithm 1's first global round client by client
and also counts, per local step and MoE layer, the (token, choice) rows
routed to each held expert.  ``dtype`` bfloat16 selects the control: the
same round with every array in bfloat16.  Two planted faults:
``capacity``, each expert keeps only the first ceil(1.25 S k / router
experts) (token, choice) rows of each sequence, as a capacity dispatch
would, and drops the rest; ``half_batch``, the second half of the pooled
batch's rows leaves the loss, which is then the mean over the rest.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

LATENT_EPS = 1e-6


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate_pairs(x, theta):
    """x (B, S, ..., r): dims (2i, 2i+1) turn by position * theta^(-2i/r)."""
    S, r = x.shape[1], x.shape[-1]
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    shape = (1, S) + (1,) * (x.ndim - 3) + (r // 2,)
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _lin(x, w, lo, t, lscale):
    y = x @ w["w"]
    if lo is not None:
        y = y + lscale * ((x @ lo[t]["a"].T) @ lo[t]["b"].T)
    return y


def _mla(x, p, lo, c):
    B, S, _ = x.shape
    H, dn, dr, dv, R = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"],
                        c["kv_lora_rank"])
    ls = c["lora_alpha"] / c["lora_rank"]
    q = _lin(x, p["q_proj"], lo, "q_proj", ls).reshape(B, S, H, dn + dr)
    ckv = x @ p["kv_a_proj"]["w"]
    lat = _rms(ckv[..., :R], p["kv_norm"]["scale"], LATENT_EPS)
    kv = _lin(lat, p["kv_b_proj"], lo, "kv_b_proj", ls).reshape(
        B, S, H, dn + dv)
    q_r = _rotate_pairs(q[..., dn:], c["rope_theta"])
    k_r = _rotate_pairs(ckv[..., R:], c["rope_theta"])          # (B, S, dr)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn])
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r)) / math.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, kv[..., dn:]).reshape(B, S, H * dv)
    return o @ p["o_proj"]["w"]


def _swiglu(x, p):
    g = x @ p["w_gate"]["w"]
    return (jax.nn.silu(g) * (x @ p["w_up"]["w"])) @ p["w_down"]["w"]


def route(x, router, k: int, scale: float):
    """(ids (..., k) of the chosen experts, their gate weights (..., k)):
    DeepSeek-V3's ``noaux_tc`` gate with one group."""
    scores = jax.nn.sigmoid(x @ router["w"])
    _, ids = jax.lax.top_k(scores + router["bias"], k)
    w = jnp.take_along_axis(scores, ids, -1)
    return ids, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


def _moe(x, p, c, capacity: bool):
    """(held experts' part + shared, rows that reach each held expert)."""
    B, S, d = x.shape
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    first, n_all = (c["deployment"]["first_expert"],
                    c["deployment"]["router_experts"])
    ids, w = route(x, p["router"], k, c["routed_scaling_factor"])
    chosen = ids[..., None] == (first + jnp.arange(E))           # (B,S,k,E)
    if capacity:
        cap = math.ceil(1.25 * S * k / n_all)
        pos = jnp.cumsum(chosen.reshape(B, S * k, E), 1).reshape(chosen.shape)
        chosen = chosen & (pos <= cap)
    comb = jnp.sum(w[..., None] * chosen, axis=2)                # (B, S, E)
    out = _swiglu(x, p["shared"])
    for e in range(E):
        g = x @ p["w_gate"][e]
        y = (jax.nn.silu(g) * (x @ p["w_up"][e])) @ p["w_down"][e]
        out = out + comb[..., e:e + 1] * y
    return out, jnp.sum(chosen, axis=(0, 1, 2))


def _stack(x, layers, lora, lo: int, hi: int, c, moe: bool, capacity: bool):
    """Layers [lo, hi) of a stacked group; ``lora`` holds the adapters of
    exactly those layers (the program's one-position tuple)."""
    sl = jax.tree.map(lambda v: v[lo:hi], layers)
    eps = c["rms_norm_eps"]

    def body(x, xs):
        p, l = xs
        x = x + _mla(_rms(x, p["norm1"]["scale"], eps), p["mixer"], l, c)
        h = _rms(x, p["norm2"]["scale"], eps)
        if moe:
            m, rows = _moe(h, p["mlp"], c, capacity)
        else:
            m, rows = _swiglu(h, p["mlp"]), jnp.zeros((0,), jnp.int32)
        return x + m, rows

    return jax.lax.scan(body, x, (sl, lora[0]["mixer"]))


def _cast(tree, dtype):
    return jax.tree.map(lambda v: v.astype(dtype), tree)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _client_grads(cj, ell, capacity, dtype, params, lora_c, lora_s, tokens,
                  labels, weight, n_total):
    """(CE sum over this client's labelled tokens / n_total, grads wrt the
    client's adapters and the server's; rows per MoE layer and held
    expert).  The client holds the leading layers and MoE layers [0, ell -
    leading); the server the rest and the head.  ``weight`` is 1 for rows
    that count and 0 for rows left out."""
    c = json.loads(cj)
    F = c["first_k_dense_replace"]
    L = c["num_hidden_layers"] - F
    params = _cast(params, dtype)

    def loss(lc, ls):
        x = params["embed"]["tok"][tokens]
        x, _ = _stack(x, params["prefix"][0], lc["prefix"], 0, F, c, False,
                      False)
        x, r1 = _stack(x, params["layers"][0], lc["layers"], 0, ell - F, c,
                       True, capacity)
        x, r2 = _stack(x, params["layers"][0], ls, ell - F, L, c, True,
                       capacity)
        x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
        logits = x @ params["embed"]["unembed"]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return (jnp.sum(nll * weight.astype(dtype)[:, None]) / n_total,
                jnp.concatenate([r1, r2]))

    (l, rows), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        lora_c, lora_s)
    return l, g, rows


def _adam(p, g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
    return p, m, v


def sfl_round(c: dict, params, lora_c, lora_s, tokens, labels, *, ell: int,
              lr: float, dtype=jnp.float32, capacity: bool = False,
              half_batch: bool = False):
    """The first global round from adapters ``lora_c`` ({"prefix",
    "layers"}, leading axis K) and ``lora_s`` (MoE layers past the split),
    split after layer ``ell``, Adam at ``lr`` on each side, FedAvg of the
    clients' adapters with equal weights.  tokens, labels: numpy
    (I, K, b, S).

    Returns host numpy trees: per-step losses (I,), the first Adam moment
    of both sides after the round, both sides' adapters after FedAvg, and
    ``rows`` (I, MoE layers, held experts): the (token, choice) rows
    routed to each held expert, summed over the clients."""
    I, K, b, S = tokens.shape
    cj = json.dumps(c, sort_keys=True)
    keep = np.ones((K, b), np.float32)
    if half_batch:
        keep.reshape(-1)[(K * b + 1) // 2:] = 0.0
    n_total = float(keep.sum() * S)
    lora_c, lora_s = _cast(lora_c, dtype), _cast(lora_s, dtype)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    mc, vc, ms, vs = zeros(lora_c), zeros(lora_c), zeros(lora_s), zeros(lora_s)
    losses, rows = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(I):
            gs, gcs, loss, r = zeros(lora_s), [], 0.0, 0
            for k in range(K):
                lk, (gc, g), rk = _client_grads(
                    cj, ell, capacity, dtype, params,
                    jax.tree.map(lambda v: v[k], lora_c), lora_s,
                    jnp.asarray(tokens[i, k]), jnp.asarray(labels[i, k]),
                    jnp.asarray(keep[k]), n_total)
                loss, r = loss + lk, r + np.asarray(rk)
                gcs.append(gc)
                gs = jax.tree.map(jnp.add, gs, g)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *gcs)
            t = float(i + 1)
            lora_c, mc, vc = _adam(lora_c, gc, mc, vc, t, lr)
            lora_s, ms, vs = _adam(lora_s, gs, ms, vs, t, lr)
            losses.append(float(loss))
            rows.append(r)
    lora_c = jax.tree.map(
        lambda v: jnp.broadcast_to(v.mean(0, keepdims=True), v.shape), lora_c)
    host = lambda t: jax.tree.map(np.asarray, t)
    return {"losses": np.asarray(losses), "m_client": host(mc),
            "m_server": host(ms), "lora_client": host(lora_c),
            "lora_server": host(lora_s), "rows": np.stack(rows)}
