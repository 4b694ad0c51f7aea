"""Expert-parallel MoE with EXPLICIT collectives via `shard_map`.

The einsum formulation in ``moe.py`` leaves the token redistribution to
GSPMD.  This module is the hand-scheduled alternative: each device routes
its local tokens, packs per-destination capacity buffers, exchanges them
with ONE ``all_to_all`` over the "model" axis (the expert-parallel
dimension), runs its local experts, and sends results back with a second
``all_to_all`` — the canonical Switch/GShard schedule, stated explicitly
rather than inferred.

Layout contract (matches the seq-parallel flow):
  x        : (B, S, d)  sharded P(dp, tp, None)
  router   : (d, E)     replicated
  experts  : (E, d, f)  sharded P(tp, None, None)   (tp owns E/tp experts)
  output   : (B, S, d)  sharded P(dp, tp, None)

Tokens that overflow the per-destination capacity are dropped (output 0
for that expert slot), like the einsum path.  Use a generous
capacity_factor to compare the two implementations exactly.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _local_moe(cfg, xb, router_w, w_gate, w_up, w_down, *, tp_size: int,
               capacity: int, tp_axis: str):
    """Per-device body.  xb: (b_l, s_l, d) local tokens."""
    b_l, s_l, d = xb.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    e_local = E // tp_size
    T = b_l * s_l
    x = xb.reshape(T, d)

    # ---- routing ----------------------------------------------------------
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, K)                     # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # ---- pack per-destination-rank capacity buffers -----------------------
    flat_ids = ids.reshape(T * K)
    flat_gates = gates.reshape(T * K)
    dest = flat_ids // e_local                               # (T*K,) tp rank
    onehot_dest = jax.nn.one_hot(dest, tp_size, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_dest, axis=0) - 1                # slot per dest
    slot = jnp.sum(pos * onehot_dest, axis=-1)
    keep = slot < capacity
    slot = jnp.where(keep, slot, capacity - 1)

    tok_idx = jnp.arange(T * K) // K
    send_x = jnp.zeros((tp_size, capacity, d), xb.dtype)
    send_eid = jnp.full((tp_size, capacity), -1, jnp.int32)  # local expert id
    send_x = send_x.at[dest, slot].set(
        jnp.where(keep[:, None], x[tok_idx], 0.0).astype(xb.dtype))
    send_eid = send_eid.at[dest, slot].set(
        jnp.where(keep, flat_ids % e_local, -1))

    # ---- exchange: tokens travel to their expert's rank --------------------
    recv_x = jax.lax.all_to_all(send_x, tp_axis, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, tp_axis, 0, 0, tiled=False)
    # recv_*: (tp_size, capacity, ...) — slice s is the buffer from rank s

    # ---- local expert FFN (dense per-local-expert dispatch) ----------------
    rx = recv_x.reshape(tp_size * capacity, d)
    reid = recv_eid.reshape(tp_size * capacity)
    disp = jax.nn.one_hot(jnp.maximum(reid, 0), e_local,
                          dtype=xb.dtype) * (reid >= 0)[:, None].astype(xb.dtype)
    xd = jnp.einsum("te,td->etd", disp, rx)                  # (e_l, T_r, d)
    hg = jnp.einsum("etd,edf->etf", xd, w_gate.astype(xb.dtype))
    hu = jnp.einsum("etd,edf->etf", xd, w_up.astype(xb.dtype))
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(xb.dtype) * hu
    yd = jnp.einsum("etf,efd->etd", h, w_down.astype(xb.dtype))
    y_tok = jnp.einsum("etd,te->td", yd, disp)               # (T_r, d)

    # ---- exchange back ------------------------------------------------------
    back = jax.lax.all_to_all(y_tok.reshape(tp_size, capacity, d),
                              tp_axis, 0, 0, tiled=False)

    # ---- unpack: gather each (token, choice) result, weight by gate --------
    out = jnp.zeros((T, d), jnp.float32)
    contrib = back[dest, slot].astype(jnp.float32)           # (T*K, d)
    contrib = jnp.where(keep[:, None], contrib, 0.0) * flat_gates[:, None]
    out = out.at[tok_idx].add(contrib)
    return out.reshape(b_l, s_l, d).astype(xb.dtype)


def apply_moe_shard_map(cfg, p: dict, x: jax.Array, mesh: Mesh, *,
                        dp_axes: Tuple[str, ...] = ("data",),
                        tp_axis: str = "model",
                        capacity_factor: float = 1.25) -> jax.Array:
    """Drop-in MoE FFN with explicit all-to-all scheduling (no aux loss)."""
    B, S, d = x.shape
    tp_size = mesh.shape[tp_axis]
    dp_size = math.prod(mesh.shape[a] for a in dp_axes)
    t_local = (B // dp_size) * (S // tp_size)
    capacity = max(1, int(math.ceil(
        t_local * cfg.experts_per_token / tp_size * capacity_factor)))

    body = functools.partial(_local_moe, cfg, tp_size=tp_size,
                             capacity=capacity, tp_axis=tp_axis)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, tp_axis, None), P(None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None)),
        out_specs=P(dp_axes, tp_axis, None),
        check_vma=False)
    return fn(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
