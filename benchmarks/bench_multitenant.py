"""Multi-tenant adapter serving benchmark: one mixed-tenant engine vs
per-tenant sequential engines, on a reduced GPT2-S.

Mixed batch vs sequential at EQUAL HBM — the same 12-request workload
over 6 distinct tenant adapters is served by (a) ONE multi-tenant engine
batching all tenants into every fused step, and (b) one single-adapter
engine PER TENANT run back to back, each sized to the same KV page pool
and base weights (only one sequential engine is live at a time, so peak
HBM matches).  Engine steps to drain are deterministic counts — the us
column carries STEPS (noise-free gate ratio).  Batching distinct tenants
is the whole point of the gather kernel: the sequential baseline pays
~num_tenants more steps.

Rows land in ``BENCH_multitenant.json`` (``benchmarks.run`` snapshots
``multitenant/``); ``check_regression.py`` gates the mixed-vs-sequential
ratio against the committed baseline.
"""
from __future__ import annotations

import jax
import numpy as np


def _workload(cfg, num_tenants, per_tenant, seed=4):
    """(tenant, prompt, max_new) rows — deterministic, round-robin."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(num_tenants * per_tenant):
        prompt = rng.integers(5, cfg.vocab_size, rng.integers(8, 20)).tolist()
        out.append((i % num_tenants, prompt, 12))
    return out


def _drain(eng, reqs, max_steps=5_000):
    for r in reqs:
        eng.submit(r)
    steps = 0
    while steps < max_steps:
        if not eng.queue and all(s is None for s in eng.slots):
            break
        eng.step()
        steps += 1
    assert all(r.done for r in reqs), "workload did not drain"
    return steps, sum(len(r.output) for r in reqs)


def _mixed_vs_sequential(emit):
    from repro.configs import get_arch
    from repro import models as M
    from repro.models.generate import SampleConfig
    from repro.serving import AdapterRegistry, Request, ServingEngine

    cfg = get_arch("gpt2-s").reduced(num_layers=2)
    params = M.init_params(cfg, jax.random.key(0))
    NT, SLOTS, MAXLEN, PS = 6, 6, 64, 16
    pages = SLOTS * (MAXLEN // PS) + 1
    adapters = [M.model.init_lora_stack(cfg, jax.random.key(100 + t))
                for t in range(NT)]
    work = _workload(cfg, NT, per_tenant=2)

    # (a) ONE engine, all tenants batched into every fused gather step
    reg = AdapterRegistry(cfg, pool_size=SLOTS)
    for t, a in enumerate(adapters):
        reg.publish(t, a)
    eng = ServingEngine(cfg, params, adapters=reg, max_slots=SLOTS,
                        max_len=MAXLEN, page_size=PS, num_pages=pages,
                        sc=SampleConfig(greedy=True))
    mixed_reqs = [Request(uid=i, prompt=p, max_new_tokens=g, tenant=t)
                  for i, (t, p, g) in enumerate(work)]
    steps_mixed, toks_mixed = _drain(eng, mixed_reqs)
    assert eng._jit_step_paged._cache_size() == 1

    # (b) one single-adapter engine per tenant, run back to back; each
    # engine has the SAME page pool / base params, and only one is live
    # at a time -> equal peak HBM
    steps_seq = toks_seq = 0
    seq_out = {}
    for t in range(NT):
        e1 = ServingEngine(cfg, params, lora=adapters[t], max_slots=SLOTS,
                           max_len=MAXLEN, page_size=PS, num_pages=pages,
                           sc=SampleConfig(greedy=True))
        reqs = [Request(uid=i, prompt=p, max_new_tokens=g)
                for i, (tt, p, g) in enumerate(work) if tt == t]
        s, k = _drain(e1, reqs)
        steps_seq += s
        toks_seq += k
        for r in reqs:
            seq_out[r.uid] = r.output
    # same workload, same tokens — and token-identical per request
    assert toks_mixed == toks_seq
    assert all(seq_out[r.uid] == r.output for r in mixed_reqs)

    # STEPS in the us column: deterministic, gate-stable
    emit("multitenant/steps_mixed", steps_mixed,
         f"unit=steps;tenants={NT};slots={SLOTS};tokens={toks_mixed};"
         f"adapter_swaps={eng.stats['adapter_swaps']}")
    emit("multitenant/steps_sequential", steps_seq,
         f"unit=steps;engines={NT};tokens={toks_seq};"
         f"mixed_speedup={steps_seq / max(steps_mixed, 1):.2f}x_steps")
    tt = eng.stats["tenant_tokens"]
    emit("multitenant/tokens_delivered", toks_mixed,
         f"unit=tokens;per_tenant={ {t: tt[t] for t in sorted(tt)} }")


def main(emit):
    _mixed_vs_sequential(emit)


if __name__ == "__main__":
    main(lambda n, t, d: print(f"{n},{t},{d}"))
