"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number beside its own limit."""
from __future__ import annotations

import math

import jax
import numpy as np


def _leaves(tree, client: bool) -> dict:
    """Per-layer leaves by name; a client tree has axes (client, layer)."""
    out = {}
    for path, v in jax.tree_util.tree_leaves_with_path(tree):
        v = np.asarray(v, np.float64)
        name = jax.tree_util.keystr(path)
        if client:
            for k in range(v.shape[0]):
                for i in range(v.shape[1]):
                    out[f"client{k}{name}[{i}]"] = v[k, i]
        else:
            for i in range(v.shape[0]):
                out[f"server{name}[{i}]"] = v[i]
    return out


def _worst_gap(prog: dict, ref: dict, names) -> tuple:
    """max over leaves of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    rn = {n: float(np.linalg.norm(ref[n])) for n in names}
    med = float(np.median(list(rn.values())))
    worst, at = 0.0, ""
    for n in names:
        g = abs(float(np.linalg.norm(prog[n])) - rn[n]) / max(rn[n], med)
        if not g <= worst:          # NaN counts as the worst
            worst, at = g, n
    return worst, at


def _entry(value, limit, **extra) -> dict:
    return {"value": float(value), "limit": float(limit), **extra}


def train(first: dict, ref: dict, lc0, ls0, limits: dict) -> dict:
    """``first``: the program's losses and host state after its first
    round; ``ref``: ``reference.sfl_round`` of the same round; lc0, ls0:
    the adapters both started from.

    * loss_gap: worst relative gap of a local step's loss;
    * grad_gap: worst leaf's gap in the norm of Adam's first moment after
      the round (the round's gradients as the optimizer got them);
    * update_gap: worst leaf's gap in the norm of the adapters' change over
      the round, FedAvg included.
    Leaves whose reference moment is under a thousandth of the median
    leaf's are nought to rounding and left out of both leaf numbers."""
    st = first["state"]
    pl, rl = np.asarray(first["losses"], np.float64), ref["losses"]
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl))) \
        if pl.shape == rl.shape else math.inf
    pm = {**_leaves(st.opt_client["m"], True),
          **_leaves(st.opt_server["m"], False)}
    rm = {**_leaves(ref["m_client"], True), **_leaves(ref["m_server"], False)}
    med = float(np.median([np.linalg.norm(v) for v in rm.values()]))
    live = [n for n in rm if np.linalg.norm(rm[n]) >= 1e-3 * med]
    grad_gap, g_at = _worst_gap(pm, rm, live)
    sub = lambda a, b: jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64), a, b)
    pd = {**_leaves(sub(st.lora_client, lc0), True),
          **_leaves(sub(st.lora_server, ls0), False)}
    rd = {**_leaves(sub(ref["lora_client"], lc0), True),
          **_leaves(sub(ref["lora_server"], ls0), False)}
    update_gap, u_at = _worst_gap(pd, rd, live)
    return {"loss_gap": _entry(loss_gap, limits["loss_gap"]),
            "grad_gap": _entry(grad_gap, limits["grad_gap"], leaf=g_at),
            "update_gap": _entry(update_gap, limits["update_gap"], leaf=u_at)}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values()
               if "limit" in c)
