"""Traffic-trace serving benchmark: paged vs slab KV under load.

Poisson load at EQUAL KV HBM on a reduced GPT2-S: the same arrival trace
(Poisson arrivals in engine-step time, lognormal prompt/output lengths)
is served by a slab engine with ``slots * max_len`` worst-case tokens and
a paged engine whose page pool holds the SAME total tokens but is shared
by 3x the slots.  The paged engine admits work that the slab queues
behind head-of-line worst-case reservations, so time-to-first-token
collapses.  TTFT rows are recorded in deterministic STEP units (the us
column holds steps — the trace and admission are fully deterministic, so
the regression-gate ratio is noise-free).

Rows land in ``BENCH_traffic.json`` (``benchmarks.run`` snapshots
``traffic/``); ``check_regression.py`` gates the p99-TTFT ratio against
the committed baseline.
"""
from __future__ import annotations

import jax
import numpy as np


def _setup():
    from repro.configs import get_arch
    from repro import models as M

    cfg = get_arch("gpt2-s").reduced(num_layers=2)
    params = M.init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, *, paged, slots, max_len, num_pages=None,
            page_size=16):
    from repro.models.generate import SampleConfig
    from repro.serving import ServingEngine

    kw = dict(page_size=page_size, num_pages=num_pages) if paged else {}
    return ServingEngine(cfg, params, max_slots=slots, max_len=max_len,
                         sc=SampleConfig(greedy=True), paged=paged, **kw)


def _kv_bytes_per_token(cfg) -> int:
    """f32 K+V bytes per cached token across the whole stack."""
    n_attn = sum(1 for p in cfg.pattern) * cfg.pattern_repeats
    return n_attn * 2 * cfg.num_kv_heads * cfg.head_dim * 4


def _trace(cfg, n=60, lam=1.5, seed=3):
    """(arrival_step, prompt, max_new) per request — Poisson arrivals,
    lognormal lengths, deterministic."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / lam)
        P = int(np.clip(rng.lognormal(3.0, 0.6), 4, 120))
        G = int(np.clip(rng.lognormal(2.5, 0.6), 2, 48))
        prompt = rng.integers(5, cfg.vocab_size, P).tolist()
        out.append((int(np.ceil(t)), prompt, G))
    return out


def _run_load(eng, trace, max_steps=5_000):
    """Serve the trace; returns (ttft_steps per request, mean live
    slots)."""
    from repro.serving import Request

    reqs, arrived_at, first_tok = {}, {}, {}
    idx, step, live_sum = 0, 0, 0
    while step < max_steps:
        while idx < len(trace) and trace[idx][0] <= step:
            at, prompt, gen = trace[idx]
            r = Request(uid=idx, prompt=prompt, max_new_tokens=gen)
            eng.submit(r)
            reqs[idx], arrived_at[idx] = r, at
            idx += 1
        if idx >= len(trace) and not eng.queue and \
                all(s is None for s in eng.slots):
            break
        eng.step()
        for uid, r in reqs.items():
            if uid not in first_tok and r.output:
                first_tok[uid] = step
        live_sum += sum(s is not None for s in eng.slots)
        step += 1
    assert all(r.done for r in reqs.values()), "trace did not drain"
    ttft = [first_tok[u] - arrived_at[u] + 1 for u in reqs]
    return ttft, live_sum / max(step, 1)


def main(emit):
    cfg, params = _setup()
    per_tok = _kv_bytes_per_token(cfg)

    # slab: 4 slots x 192 tokens = 768 worst-case tokens.
    # paged: a 48-page x 16-token pool = the SAME 768 tokens of HBM
    # (null page included), shared by 12 slots — 3x the admission width.
    max_len, PS, pages = 192, 16, 48
    slab_tokens = 4 * max_len
    paged_tokens = pages * PS
    assert slab_tokens == paged_tokens

    trace = _trace(cfg)
    results = {}
    for name, eng in (
        ("slab", _engine(cfg, params, paged=False, slots=4,
                         max_len=max_len)),
        ("paged", _engine(cfg, params, paged=True, slots=12,
                          max_len=max_len, num_pages=pages, page_size=PS)),
    ):
        ttft, conc = _run_load(eng, trace)
        results[name] = conc
        # TTFT rows carry deterministic STEPS in the us column
        emit(f"traffic/ttft_p50_{name}", float(np.percentile(ttft, 50)),
             "unit=steps")
        emit(f"traffic/ttft_p99_{name}", float(np.percentile(ttft, 99)),
             "unit=steps")
        emit(f"traffic/concurrency_{name}", conc,
             f"unit=mean_live_slots;requests={len(trace)}")
        emit(f"traffic/peak_kv_bytes_{name}",
             (slab_tokens if name == "slab" else paged_tokens) * per_tok,
             f"unit=bytes;tokens={slab_tokens};equal_hbm=true")
    emit("traffic/concurrency_gain", 0.0,
         f"paged_over_slab={results['paged'] / max(results['slab'], 1e-9):.2f}x")


if __name__ == "__main__":
    main(lambda n, t, d: print(f"{n},{t},{d}"))
