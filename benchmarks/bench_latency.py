"""Paper Figs. 5-8: total training latency vs (bandwidth | client compute |
server compute | transmit power), proposed BCD allocator vs baselines a-d.

Analytic over the Section V delay model with the Table II wireless setup
and GPT2-S workloads — the paper's own evaluation protocol.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.configs import DEFAULT_SYSTEM, get_arch
from repro.core import (Problem, baseline, bcd_minimize_delay, objective,
                        sample_clients)
from repro.launch.engine import modeled_total_seconds

SEQ, BATCH, I = 512, 16, 12
N_BASELINE_SEEDS = 4


def _prob(sys_cfg, seed=0):
    envs = tuple(sample_clients(sys_cfg, seed))
    return Problem(cfg=get_arch("gpt2-s"), sys_cfg=sys_cfg, envs=envs,
                   seq_len=SEQ, batch=BATCH, local_steps=I)


def _eval(prob):
    """proposed: the allocator's pick, priced by the same eq. 17 model the
    engine logs per round; baselines a-d: the paper's comparison points."""
    row = {}
    alloc, _ = bcd_minimize_delay(prob)
    row["proposed"] = modeled_total_seconds(prob, alloc)
    for w in "abcd":
        ts = [objective(prob, baseline(prob, w, np.random.default_rng(s)))
              for s in range(N_BASELINE_SEEDS)]
        row[f"baseline_{w}"] = float(np.mean(ts))
    return row


SWEEPS = {
    # Fig 5: total bandwidth per link
    "fig5_bandwidth": [
        ("bw_%.0fkHz" % (bw / 1e3),
         lambda bw=bw: dataclasses.replace(DEFAULT_SYSTEM,
                                           total_bandwidth_hz=bw))
        for bw in (250e3, 500e3, 1e6, 2e6)
    ],
    # Fig 6: client compute (FLOPs per cycle = 1/kappa)
    "fig6_client_compute": [
        ("kappa_1_%d" % inv,
         lambda inv=inv: dataclasses.replace(DEFAULT_SYSTEM,
                                             kappa_client=1.0 / inv))
        for inv in (512, 1024, 2048, 4096)
    ],
    # Fig 7: main server compute
    "fig7_server_compute": [
        ("fs_%.0fGHz" % (f / 1e9),
         lambda f=f: dataclasses.replace(DEFAULT_SYSTEM, f_server_hz=f))
        for f in (2.5e9, 5e9, 10e9, 20e9)
    ],
    # Fig 8: per-client max transmit power
    "fig8_power": [
        ("pmax_%.1fdBm" % p,
         lambda p=p: dataclasses.replace(DEFAULT_SYSTEM, p_max_dbm=p))
        for p in (30.0, 35.0, 41.76, 45.0)
    ],
}


def main(emit):
    for sweep, points in SWEEPS.items():
        for label, mk in points:
            t0 = time.time()
            row = _eval(_prob(mk()))
            us = (time.time() - t0) * 1e6
            derived = ";".join(f"{k}={v:.1f}" for k, v in row.items())
            red = 100 * (1 - row["proposed"] / row["baseline_a"])
            emit(f"{sweep}/{label}", us, derived + f";reduction_vs_a={red:.1f}%")


if __name__ == "__main__":
    main(lambda n, t, d: print(f"{n},{t},{d}"))
