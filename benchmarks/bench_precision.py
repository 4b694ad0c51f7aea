"""Precision benchmarks: what quantizing the split boundary buys and costs.

Two deterministic row groups land in ``BENCH_precision.json``:

  delay     the allocator's bits axis — per-client BCD on the edge
            scenario with ``bits_candidates=(16,)`` (the pre-precision
            problem) vs ``(4, 8, 16)``.  Both rows are modeled seconds
            from the same deterministic search, so the ratio is
            noise-free; the bits axis must strictly reduce the modeled
            round delay (asserted) and ``check_regression.py`` gates the
            ratio against the committed baseline.

  loss      one fixed memorization episode (K=4, shared constant batch,
            tiny vocab) trained twice from the same init: full-precision
            boundary vs int8 activations + int8 gradients with
            stochastic rounding and error feedback.  Final eval losses
            in milli-units; the quantized run must land within 2% of
            f32 (asserted — the paper-level claim that an int8 boundary
            is convergence-neutral), and the ratio is gated.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

K, B, S, I = 4, 1, 8, 2
ROUNDS = 24
LR = 1e-2


def _timed(fn, repeats: int = 3):
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


def _edge_problem(bits_candidates):
    from repro.configs import DEFAULT_SYSTEM, get_arch
    from repro.core import Problem, sample_clients

    edge_sys = dataclasses.replace(DEFAULT_SYSTEM, total_bandwidth_hz=50e6,
                                   f_server_hz=1.0e9,
                                   f_client_hz_range=(0.3e9, 3.0e9))
    envs = tuple(sample_clients(edge_sys, 0))
    return Problem(cfg=get_arch("gpt2-s"), sys_cfg=edge_sys, envs=envs,
                   seq_len=512, batch=16, local_steps=12,
                   bits_candidates=bits_candidates)


def _episode_setup():
    from repro.configs import DEFAULT_SYSTEM, get_arch
    from repro.core import (Problem, bcd_minimize_delay_per_client,
                            sample_clients)
    from repro import models as M

    sys_cfg = dataclasses.replace(
        DEFAULT_SYSTEM, num_clients=K, total_bandwidth_hz=50e6,
        f_server_hz=0.4e9, f_client_hz_range=(0.2e9, 5.0e9))
    env0 = sample_clients(sys_cfg, 3)[0]
    prob = Problem(cfg=get_arch("gpt2-s").reduced(num_layers=2, vocab=64),
                   sys_cfg=sys_cfg, envs=tuple([env0] * K), seq_len=S,
                   batch=B, local_steps=I, rank_candidates=(8,))
    alloc, _ = bcd_minimize_delay_per_client(prob)
    params = M.init_params(prob.cfg, jax.random.key(0))
    row = np.random.default_rng(0).integers(
        0, prob.cfg.vocab_size, (1, 1, B, S)).astype(np.int32)
    tokens = np.broadcast_to(row, (I, K, B, S)).copy()
    batch = {"tokens": tokens, "labels": tokens.copy()}
    ev_batch = {"tokens": jnp.asarray(tokens[0, 0]),
                "labels": jnp.asarray(tokens[0, 0])}
    return prob, alloc, params, batch, ev_batch


def _episode(prob, alloc, params, batch, ev_batch, *, precision):
    from repro.core import SflLLM
    from repro.models import default_train_runtime
    from repro.optim import adamw

    rt = default_train_runtime()
    if precision is not None:
        rt = rt.replace(precision=precision)
    sfl = SflLLM.from_allocation(prob, alloc, params, optimizer=adamw(LR),
                                 rt=rt)
    st = sfl.init_state(sfl.init_lora(jax.random.key(7)))
    t0 = time.time()
    for _ in range(ROUNDS):
        st, _ = sfl.train_round(st, batch, [1.0] * K)
    wall = time.time() - t0
    assert sfl._round_traces == 1, "episode retraced"
    return float(sfl.eval_loss(st, ev_batch)), wall


def main(emit) -> None:
    from repro.core import bcd_minimize_delay_per_client
    from repro.precision import PrecisionConfig

    # ---- allocator: the bits axis vs the pre-precision search -------------
    (a16, h16), t16 = _timed(
        lambda: bcd_minimize_delay_per_client(_edge_problem((16,))),
        repeats=1)
    (ab, hb), tb = _timed(
        lambda: bcd_minimize_delay_per_client(_edge_problem((4, 8, 16))),
        repeats=1)
    assert hb[-1] < h16[-1], \
        f"bits axis failed to reduce modeled delay: {hb[-1]} vs {h16[-1]}"
    assert ab.bits_k is not None and (ab.bits_k < 16).any()
    emit("precision/delay_bits16", h16[-1] * 1e6,
         f"unit=model_s*1e6;ell_k={'/'.join(map(str, a16.ell_k))};"
         f"wall_s={t16:.1f}")
    emit("precision/delay_bits_opt", hb[-1] * 1e6,
         f"unit=model_s*1e6;gain={100 * (1 - hb[-1] / h16[-1]):.1f}%;"
         f"bits_k={'/'.join(map(str, ab.bits_k))};wall_s={tb:.1f}")

    # ---- episode: int8 boundary is convergence-neutral --------------------
    prob, alloc, params, batch, ev_batch = _episode_setup()
    f32_loss, w_f32 = _episode(prob, alloc, params, batch, ev_batch,
                               precision=None)
    q_prec = PrecisionConfig(act_bits=8, grad_bits=8,
                             stochastic_rounding=True, error_feedback=True)
    q_loss, w_q = _episode(prob, alloc, params, batch, ev_batch,
                           precision=q_prec)
    assert q_loss <= 1.02 * f32_loss, \
        f"int8 boundary not convergence-neutral: {q_loss:.4f} " \
        f"vs f32 {f32_loss:.4f}"
    emit("precision/loss_f32_milli", 1e3 * f32_loss,
         f"unit=milli_loss;rounds={ROUNDS};wall_s={w_f32:.1f}")
    emit("precision/loss_quant_milli", 1e3 * q_loss,
         f"unit=milli_loss;vs_f32={q_loss / max(f32_loss, 1e-9):.3f}x;"
         f"act=8;grad=8;sr=1;ef=1;wall_s={w_q:.1f}")


if __name__ == "__main__":
    main(lambda n, t, d: print(f"{n},{t},{d}"))
