"""Dynamic-rounds benchmarks: the cost of time-varying fleets.

Rows land in BENCH_dynamic.json (archived by the CI kernel-parity job):
modeled training delay over a block-fading episode — static allocation vs
the drift-triggered warm re-allocation loop — with the dropout rate under
a paper-style deadline, and the allocator's cold and warm wall time.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.configs import DEFAULT_SYSTEM, get_arch
from repro.core import (Problem, as_hetero, bcd_minimize_delay_per_client,
                        objective_het, reallocate_warm, sample_clients)
from repro.core.channel import FadingProcess
from repro.core.latency import client_round_seconds_host


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _bench_adaptive_allocation(emit) -> None:
    # wireless-bound regime (10 MHz shared uplink, fast clients): fading
    # actually moves the objective, so drift triggers fire
    sys_cfg = dataclasses.replace(
        DEFAULT_SYSTEM, num_clients=5, total_bandwidth_hz=10e6,
        f_server_hz=3.0e9, f_client_hz_range=(2.0e9, 8.0e9))
    envs = tuple(sample_clients(sys_cfg, 0))
    prob = Problem(cfg=get_arch("gpt2-s"), sys_cfg=sys_cfg, envs=envs,
                   seq_len=512, batch=16, local_steps=12)
    (alloc0, _), t_cold = _timed(lambda: bcd_minimize_delay_per_client(prob))
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
                lambda p=prob_r, c=cur: reallocate_warm(p, c, max_sweeps=1))
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
    _bench_adaptive_allocation(emit)
