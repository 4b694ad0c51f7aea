"""Dynamic-rounds benchmarks: the cost of time-varying fleets.

Rows land in BENCH_dynamic.json (archived by the CI kernel-parity job and
gated by benchmarks/check_regression.py):

* masked-round overhead — ``train_round`` (the always-masked executable
  every caller now runs) vs the legacy unmasked round graph, same fleet;
* deadline-dropout round wall time + the trace counts over a faded
  episode (must stay 1 round trace / 1 mask trace);
* modeled training delay over a block-fading episode: static allocation
  vs the drift-triggered warm re-allocation loop, with the dropout rate
  under a paper-style deadline.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models as M
from repro.configs import DEFAULT_SYSTEM, TrainConfig, get_arch
from repro.core import (Problem, RoundDynamics, SflLLM, as_hetero,
                        bcd_minimize_delay_per_client, objective_het,
                        reallocate_warm, sample_clients)
from repro.core.channel import FadingProcess
from repro.core.latency import client_round_seconds_host
from repro.optim import adamw

K, B, S, I = 4, 2, 64, 4


def _timed(fn, repeats: int = 5):
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


def _bench_round_overhead(emit) -> None:
    cfg = get_arch("gpt2-s").reduced(num_layers=4)
    key = jax.random.key(0)
    params = M.init_params(cfg, key)
    lora = M.init_lora_stack(cfg, jax.random.key(7))
    tc = TrainConfig(num_clients=K, batch_size=B, local_steps=I)
    sfl = SflLLM(cfg, params, ell_c=2, train_cfg=tc, optimizer=adamw(1e-3),
                 donate=False)
    state = sfl.init_state(lora)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (I, K, B, S)).astype(np.int32)
    rb = {"tokens": tokens, "labels": tokens.copy()}
    batches = {k: jnp.asarray(v) for k, v in rb.items()}
    weights = jnp.ones(K, jnp.float32)

    def legacy():
        st, m = sfl._jit_round(sfl.base, state, batches, weights)
        jax.block_until_ready(m["loss"])
        return m

    def masked():
        st, m = sfl.train_round(state, rb, [1.0] * K)
        jax.block_until_ready(m["loss"])
        return m

    legacy()                                 # compile the baseline graph
    base_traces = sfl._round_traces          # the legacy jit counts too
    masked()
    _, t_legacy = _timed(legacy)
    _, t_masked = _timed(masked)
    emit("dynamic/round_wall_legacy", t_legacy * 1e6,
         f"I={I},K={K},b={B},S={S}")
    emit("dynamic/round_wall_masked", t_masked * 1e6,
         f"overhead={t_masked / max(t_legacy, 1e-12):.3f}x")

    # a fading + deadline episode: channel changes every round, one trace
    kappa = jnp.full((K,), 1.0, jnp.float32)
    f_hz = jnp.full((K,), 1e9, jnp.float32)
    rng = np.random.default_rng(1)

    def faded_round():
        dyn = RoundDynamics(
            rates_main=jnp.asarray(rng.uniform(1e4, 1e6, K), jnp.float32),
            rates_fed=jnp.asarray(rng.uniform(1e4, 1e6, K), jnp.float32),
            f_hz=f_hz, kappa=kappa, deadline_s=jnp.float32(1e3))
        st, m = sfl.train_round(state, rb, [1.0] * K, dynamics=dyn)
        jax.block_until_ready(m["loss"])
        return m

    faded_round()
    _, t_dyn = _timed(faded_round)
    round_traces = sfl._round_traces - base_traces
    emit("dynamic/round_wall_deadline", t_dyn * 1e6,
         f"round_traces={round_traces},mask_traces={sfl._mask_traces}")
    assert round_traces == 1, "dynamic rounds retraced"


def _bench_adaptive_allocation(emit) -> None:
    # wireless-bound regime (10 MHz shared uplink, fast clients): fading
    # actually moves the objective, so drift triggers fire
    sys_cfg = dataclasses.replace(
        DEFAULT_SYSTEM, num_clients=5, total_bandwidth_hz=10e6,
        f_server_hz=3.0e9, f_client_hz_range=(2.0e9, 8.0e9))
    envs = tuple(sample_clients(sys_cfg, 0))
    prob = Problem(cfg=get_arch("gpt2-s"), sys_cfg=sys_cfg, envs=envs,
                   seq_len=512, batch=16, local_steps=12)
    (alloc0, _), t_cold = _timed(
        lambda: bcd_minimize_delay_per_client(prob), repeats=1)
    alloc0 = as_hetero(prob, alloc0)
    emit("dynamic/alloc_cold_wall", t_cold * 1e6, "full per-client BCD")

    fading = FadingProcess(envs, std_db=6.0, rho=0.5, rng=0)
    rounds = 10
    drift = 0.05
    t_static = t_adaptive = 0.0
    realloc_walls = []
    reallocs = drops = 0
    cur, ref = alloc0, objective_het(prob, alloc0)
    from repro.core.latency import workload_tables
    tables = workload_tables(prob.cfg, prob.seq_len)
    deadline = 1.05 * client_round_seconds_host(
        tables, alloc0.ell_k, alloc0.rank_k,
        np.array([e.f_hz for e in envs]),
        np.array([e.kappa for e in envs]),
        alloc0.rates_main(sys_cfg, envs), alloc0.rates_fed(sys_cfg, envs),
        prob.batch, prob.local_steps).max()
    for _ in range(rounds):
        envs_r = tuple(fading.step())
        prob_r = prob.with_envs(envs_r)
        t_static += objective_het(prob_r, alloc0)
        t_keep = objective_het(prob_r, cur)
        if t_keep > (1 + drift) * ref:
            (cur, _), w = _timed(
                lambda p=prob_r, c=cur: reallocate_warm(p, c, max_sweeps=1),
                repeats=1)
            realloc_walls.append(w)
            ref = objective_het(prob_r, cur)
            reallocs += 1
            t_adaptive += ref
        else:
            t_adaptive += t_keep
        t_k = client_round_seconds_host(
            tables, cur.ell_k, cur.rank_k,
            np.array([e.f_hz for e in envs_r]),
            np.array([e.kappa for e in envs_r]),
            cur.rates_main(sys_cfg, envs_r), cur.rates_fed(sys_cfg, envs_r),
            prob.batch, prob.local_steps)
        drops += int((t_k > deadline).sum())
    gain = 100.0 * (1.0 - t_adaptive / max(t_static, 1e-12))
    emit("dynamic/modeled_static_fleet", t_static * 1e6,
         f"rounds={rounds},fade=6dB,rho=0.5")
    emit("dynamic/modeled_adaptive_fleet", t_adaptive * 1e6,
         f"gain={gain:.1f}%,reallocs={reallocs}")
    if realloc_walls:
        emit("dynamic/realloc_warm_wall", np.mean(realloc_walls) * 1e6,
             f"vs_cold={t_cold / np.mean(realloc_walls):.1f}x")
    emit("dynamic/dropout_rate", 0.0,
         f"dropped={drops}/{rounds * len(envs)}"
         f",deadline_factor=1.05")


def main(emit) -> None:
    _bench_round_overhead(emit)
    _bench_adaptive_allocation(emit)
