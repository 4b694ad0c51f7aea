#!/usr/bin/env python3
"""Chip smoke test: drive the system's two main paths once on a TPU at
GPT2-S published widths (12 layers, d_model 768, 12 heads, d_ff 3072,
vocab 50257), with random weights from a seed and the in-repo synthetic
E2E corpus, and check what comes out.

    python chip_smoke.py             # one chip: train, serve, kernels
    python chip_smoke.py --chips 4   # only the client-sharded SFL round
                                     # on four chips vs the same round on
                                     # one device of the same process

Phases (one chip):
  train    SflLLM.train_round through launch.engine.Trainer (the path of
           ``repro.launch.train --mode sfl``) on the default fused-kernel
           runtime; losses finite and falling, and the first step's loss
           agrees with the plain einsum runtime;
  serve    the paged, fused ServingEngine of ``repro.launch.serve``, with
           one shared adapter and then three tenants over an
           AdapterRegistry; every request completes, one compiled step;
  kernels  each main-path Pallas kernel against its jnp oracle at the
           shapes above, the oracle at ``highest`` matmul precision.

Every time printed is a smoke timing, not a benchmark result.  The last
line of standard output is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed; without a TPU the script exits
non-zero before running anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# K clients x batch b x sequence S, I local steps per global round.  The
# fused round at K=3, b=4, S=512 compiles for a v5e with 11.0 GB of
# temporaries next to 0.65 GB of frozen weights (16 GB of HBM).
TRAIN = dict(clients=3, batch=4, seq=512, local_steps=3, rounds=2,
             lr=1e-3, rank=4)
# --chips 4: K a multiple of 4; the unsharded reference pools K*b = 12
# sequences on one device, as many as the one-chip train phase
SHARDED = dict(clients=4, batch=3, seq=512, local_steps=3, rounds=2,
               lr=1e-3, rank=4)
SERVE = dict(requests=8, slots=4, max_len=128, page_size=16,
             prompt_max=64, gen=16, tenants=3, rank=4)

# Tolerances, each with its reason.
# The fused kernels and the einsum path compute the same f32 math; on a TPU
# the einsum's XLA matmuls take bf16 passes at default precision, so the
# two first-step losses (~10.8 nats) differ by rounding only.
FUSED_VS_EINSUM_LOSS = 2e-2
# Sharding the client axis changes only the order of the cross-device
# reductions (server gradient, FedAvg); f32 rounding carried through a few
# Adam steps stays far below this.
SHARDED_VS_ONE_LOSS = 2e-3
# Kernel vs oracle, as max|kernel - oracle| / max|oracle|: f32 operands may
# take bf16 MXU passes (8-bit mantissa) with f32 accumulation, over
# reductions of 64 to 3072 terms of unit-scale data.
KERNEL_REL_ERR = {"lora_matmul": 2e-2, "lora_matmul_bwd": 2e-2,
                  "lora_matmul_gathered": 2e-2, "flash_decode": 2e-2,
                  "paged_decode": 2e-2, "flash_attention_train": 2e-2,
                  "flash_attention_train_bwd": 2e-2}


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def model_config():
    from repro.configs import get_arch
    return get_arch("gpt2-s")


def peak_bytes(dev) -> str:
    """Peak device memory: buffers (``peak_bytes_in_use``) and the runtime's
    reservation, which holds the executables' temporaries
    (``peak_bytes_reserved``)."""
    stats = dev.memory_stats() or {}
    return ", ".join(
        f"{k} " + ("not reported" if stats.get(k) is None
                   else f"{stats[k] / 2**30:.2f} GiB")
        for k in ("peak_bytes_in_use", "peak_bytes_reserved"))


class CompileLog:
    """JAX's own compile events (``jax.monitoring``), summed per phase:
    seconds tracing, lowering and compiling (a persistent-cache hit counts
    its load time as compile time), and persistent-cache hits / misses.
    JAX counts a miss when it writes the entry, so compiles faster than
    ``jax_persistent_cache_min_compile_time_secs`` count as neither."""

    _DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration": "compile"}
    _COUNTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        from jax import monitoring
        self._reset()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _reset(self):
        self.totals = {"trace": 0.0, "lower": 0.0, "compile": 0.0,
                       "hits": 0, "misses": 0}

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.totals[self._DURATIONS[event]] += secs

    def _on_event(self, event, **_):
        if event in self._COUNTS:
            self.totals[self._COUNTS[event]] += 1

    def take(self) -> str:
        t = self.totals
        self._reset()
        return (f"trace {t['trace']:.1f}s, lower {t['lower']:.1f}s, "
                f"compile or cache load {t['compile']:.1f}s; persistent "
                f"cache {t['hits']} hits / {t['misses']} misses")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _fleet(cfg, sz):
    """E2E partitions over K clients, the allocator's split point, and the
    round-ordered local-step batches — as ``repro.launch.train`` builds
    them."""
    from repro.configs import DEFAULT_SYSTEM
    from repro.core import Problem, bcd_minimize_delay, sample_clients
    from repro.data import (WordTokenizer, e2e_splits, iid_partition,
                            sfl_batches)

    train, _, _ = e2e_splits(4000, 400, 400, seed=SEED)
    tok = WordTokenizer.from_corpus([e.text for e in train])
    check(tok.vocab_size <= cfg.vocab_size, "tokenizer outgrows the vocab")
    parts = [np.array(train, dtype=object)[idx]
             for idx in iid_partition(len(train), sz["clients"], SEED)]
    data = sfl_batches(tok, parts, sz["batch"], sz["seq"], SEED)
    steps = [next(data) for _ in range(sz["rounds"] * sz["local_steps"])]
    envs = tuple(sample_clients(DEFAULT_SYSTEM, SEED))
    prob = Problem(cfg=cfg, sys_cfg=DEFAULT_SYSTEM, envs=envs,
                   seq_len=sz["seq"], batch=sz["batch"],
                   local_steps=sz["local_steps"],
                   rank_candidates=(sz["rank"],))
    alloc, _ = bcd_minimize_delay(prob, rank0=sz["rank"])
    return parts, steps, int(alloc.ell_c)


def _trainer(cfg, params, ell, sz, *, rt=None, mesh=None):
    from repro.configs import TrainConfig
    from repro.core.sfl import SflLLM
    from repro.optim import adamw

    tc = TrainConfig(num_clients=sz["clients"], batch_size=sz["batch"],
                     local_steps=sz["local_steps"], learning_rate=sz["lr"])
    return SflLLM(cfg, params, ell_c=ell, train_cfg=tc,
                  optimizer=adamw(sz["lr"]), rt=rt, mesh=mesh)


def _fit(cfg, params, lora, parts, steps, ell, sz, *, mesh=None):
    """Run the rounds through Trainer; returns (history, round walls)."""
    from repro.launch.engine import SflRound, Trainer

    sfl = _trainer(cfg, params, ell, sz, mesh=mesh)
    walls, last = [], [time.perf_counter()]

    def timed(e, state, hist):
        now = time.perf_counter()
        walls.append(now - last[0])
        last[0] = now

    trainer = Trainer(SflRound(sfl, [len(p) for p in parts]),
                      local_steps=sz["local_steps"], log_every=1,
                      callback=timed)
    state, hist = trainer.fit(sfl.init_state(lora), iter(steps),
                              global_rounds=sz["rounds"])
    return hist, walls, sfl, state


def _round_memory(sfl, state, sz) -> str:
    """memory_analysis() of the round executable the Trainer ran (the
    lowering is the jitted round's own, so the compile is a cache hit)."""
    import jax
    import jax.numpy as jnp

    K, I = sz["clients"], sz["local_steps"]
    tok = jax.ShapeDtypeStruct((I, K, sz["batch"], sz["seq"]), jnp.int32)
    ma = sfl._jit_round_part.lower(
        sfl.base, state, {"tokens": tok, "labels": tok}, jnp.ones(K),
        jnp.ones(K), None).compile().memory_analysis()
    return ", ".join(f"{f.replace('_size_in_bytes', '')} "
                     f"{getattr(ma, f) / 2**20:.1f} MiB"
                     for f in ("argument_size_in_bytes", "temp_size_in_bytes",
                               "output_size_in_bytes",
                               "generated_code_size_in_bytes"))


def phase_train(cfg, dev) -> None:
    import jax

    from repro.models import init_lora_stack, init_params
    from repro.models.stack import default_train_runtime

    sz = TRAIN
    cfg = cfg.replace(lora_rank=sz["rank"])
    parts, steps, ell = _fleet(cfg, sz)
    print(f"[train] K={sz['clients']} b={sz['batch']} S={sz['seq']} "
          f"I={sz['local_steps']} rounds={sz['rounds']} split={ell} "
          f"rank={sz['rank']} lr={sz['lr']}")
    params = init_params(cfg, jax.random.key(SEED))
    lora = init_lora_stack(cfg, jax.random.key(SEED + 1), sz["rank"])

    # the plain path: einsum projections, the same first local step
    plain = _trainer(cfg, params, ell, sz,
                     rt=default_train_runtime().replace(dense_impl="einsum"))
    t0 = time.perf_counter()
    _, m = plain.local_step(plain.init_state(lora), steps[0])
    einsum_loss = float(m["loss"])
    print(f"[train] einsum first step: loss {einsum_loss!r} "
          f"({time.perf_counter() - t0:.1f}s smoke timing, compile incl.)")
    del plain, m
    gc.collect()

    hist, walls, sfl, state = _fit(cfg, params, lora, parts, steps, ell, sz)
    print("[train] fused losses: " + " ".join(repr(x) for x in hist.losses))
    print("[train] round walls (smoke timings; round 1 includes compile): "
          + " ".join(f"{w:.2f}s" for w in walls))
    print(f"[train] round executable: {_round_memory(sfl, state, sz)}")
    del sfl, state
    from repro.kernels.lora_matmul import tune
    print(f"[train] lora_matmul tiles (M, K, N, r, dtype, w dtype) -> "
          f"(bm, bn, bk): {tune._CACHE}")
    print(f"[train] peak so far: {peak_bytes(dev)}")
    check(bool(np.all(np.isfinite(hist.losses))), "non-finite loss")
    check(hist.round_losses[-1] < hist.round_losses[0],
          f"round mean loss did not fall: {hist.round_losses}")
    diff = abs(hist.losses[0] - einsum_loss)
    print(f"[train] fused vs einsum first-step |dloss| = {diff!r} "
          f"(tolerance {FUSED_VS_EINSUM_LOSS})")
    check(diff <= FUSED_VS_EINSUM_LOSS, "fused and einsum losses disagree")


def phase_sharded(cfg, dev) -> None:
    """The client-sharded round over a 4-device ("clients",) mesh against
    the same round on one device."""
    import jax

    from repro.launch.mesh import make_client_mesh
    from repro.models import init_lora_stack, init_params

    sz = SHARDED
    cfg = cfg.replace(lora_rank=sz["rank"])
    parts, steps, ell = _fleet(cfg, sz)
    print(f"[sharded] K={sz['clients']} b={sz['batch']} S={sz['seq']} "
          f"I={sz['local_steps']} rounds={sz['rounds']} split={ell}")
    params = init_params(cfg, jax.random.key(SEED))
    lora = init_lora_stack(cfg, jax.random.key(SEED + 1), sz["rank"])
    one, walls1, _, _ = _fit(cfg, params, lora, parts, steps, ell, sz)
    print("[sharded] one-device losses: "
          + " ".join(repr(x) for x in one.losses))
    print("[sharded] one-device round walls (smoke timings): "
          + " ".join(f"{w:.2f}s" for w in walls1))
    gc.collect()
    mesh = make_client_mesh()
    check(mesh.shape["clients"] == 4,
          f"client mesh has {mesh.shape['clients']} devices, not 4")
    four, walls4, _, _ = _fit(cfg, params, lora, parts, steps, ell, sz,
                              mesh=mesh)
    print("[sharded] 4-device losses:   "
          + " ".join(repr(x) for x in four.losses))
    print("[sharded] 4-device round walls (smoke timings): "
          + " ".join(f"{w:.2f}s" for w in walls4))
    diff = float(np.max(np.abs(np.asarray(one.losses)
                               - np.asarray(four.losses))))
    print(f"[sharded] max per-step |dloss| = {diff!r} "
          f"(tolerance {SHARDED_VS_ONE_LOSS})")
    print(f"[sharded] peak (device 0): {peak_bytes(dev)}")
    check(bool(np.all(np.isfinite(four.losses))), "non-finite loss")
    check(diff <= SHARDED_VS_ONE_LOSS, "sharded round disagrees")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _serve(eng, prompts, gen, tenants=0):
    from repro.serving import Request

    reqs = [Request(uid=i, prompt=p, max_new_tokens=gen,
                    tenant=i % tenants if tenants else 0)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    t0, steps = time.perf_counter(), 0
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
        check(steps <= len(reqs) * (gen + 1), "engine stopped making progress")
    return reqs, steps, time.perf_counter() - t0


def _check_served(tag, eng, reqs, gen) -> None:
    for r in reqs:
        check(r.error is None, f"{tag}: request {r.uid} failed: {r.error}")
        check(len(r.output) == gen,
              f"{tag}: request {r.uid} has {len(r.output)} of {gen} tokens")
    n = eng._jit_step_paged._cache_size()
    print(f"[serve] {tag}: paged step compiled {n} time(s), "
          f"{eng.prefill_compiles()} prefill chunk program(s)")
    check(n == 1, f"{tag}: fused paged step compiled {n} times")


def phase_serve(cfg, dev) -> None:
    import jax

    from repro.models import init_lora_stack, init_params
    from repro.models.generate import SampleConfig
    from repro.serving import AdapterRegistry, ServingEngine

    sz = SERVE
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(5, cfg.vocab_size,
                            int(rng.integers(4, sz["prompt_max"] + 1))
                            ).tolist() for _ in range(sz["requests"])]
    print(f"[serve] {sz['requests']} requests over {sz['slots']} slots, "
          f"prompts {min(map(len, prompts))}..{max(map(len, prompts))} "
          f"tokens, {sz['gen']} new each, max_len {sz['max_len']}, "
          f"page {sz['page_size']}, greedy")
    params = init_params(cfg, jax.random.key(SEED))
    lora = init_lora_stack(cfg, jax.random.key(SEED + 1), sz["rank"])
    kw = dict(max_slots=sz["slots"], max_len=sz["max_len"],
              sc=SampleConfig(greedy=True), seed=SEED,
              page_size=sz["page_size"])

    eng = ServingEngine(cfg, params, lora=lora, **kw)
    check(eng.paged, "the default engine is not paged")
    reqs, steps, wall = _serve(eng, prompts, sz["gen"])
    print(f"[serve] paged: {steps} engine steps in {wall:.2f}s "
          f"(smoke timing, compile incl.)")
    _check_served("paged", eng, reqs, sz["gen"])

    slab = ServingEngine(cfg, params, lora=lora, paged=False, **kw)
    sreqs, _, _ = _serve(slab, prompts, sz["gen"])
    same = sum(a == b for r, s in zip(reqs, sreqs)
               for a, b in zip(r.output, s.output))
    print(f"[serve] paged vs slab token agreement: {same}/"
          f"{sz['requests'] * sz['gen']} (different decode kernels; "
          f"random-init argmax may flip)")
    del eng, slab
    gc.collect()

    registry = AdapterRegistry(cfg, pool_size=max(sz["slots"],
                                                  min(sz["tenants"], 8)),
                               rank=sz["rank"])
    for t in range(sz["tenants"]):
        registry.publish(t, init_lora_stack(
            cfg, jax.random.key(SEED + 1 + t), sz["rank"]))
    eng = ServingEngine(cfg, params, adapters=registry, **kw)
    reqs, steps, wall = _serve(eng, prompts, sz["gen"],
                               tenants=sz["tenants"])
    print(f"[serve] multi-tenant ({sz['tenants']} tenants, pool "
          f"{registry.pool_size}): {steps} engine steps in {wall:.2f}s "
          f"(smoke timing, compile incl.), tokens per tenant "
          f"{eng.stats['tenant_tokens']}")
    _check_served("multi-tenant", eng, reqs, sz["gen"])
    from repro.kernels.flash_attention import tune as ft
    from repro.kernels.lora_matmul import tune as lt
    print(f"[serve] tiles: gather {lt._GATHER_CACHE}, "
          f"paged decode {ft._PAGED_CACHE}, flash decode {ft._CACHE}")
    print(f"[serve] peak so far: {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def phase_kernels(cfg, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import (flash_attention_train,
                                               flash_decode, paged_decode)
    from repro.kernels.lora_matmul import (lora_matmul, lora_matmul_gathered,
                                           lora_matmul_ref)
    from repro.models.attention import naive_attention

    d, ff, H = cfg.d_model, cfg.d_ff, cfg.num_heads
    hd = d // H
    r, scale = TRAIN["rank"], cfg.lora_alpha / TRAIN["rank"]
    M = TRAIN["batch"] * TRAIN["seq"]           # rows per client projection
    B, L, PS = SERVE["slots"], SERVE["max_len"], SERVE["page_size"]
    ks = iter(jax.random.split(jax.random.key(SEED + 7), 16))
    nrm = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)

    def rel(got, ref):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                     1e-30))

    errs = {}
    x, w = nrm((M, d)), nrm((d, ff)) * d ** -0.5
    a, b = nrm((r, d)) * r ** -0.5, nrm((ff, r)) * 0.1
    g = nrm((M, ff))

    # every array is an argument of the jitted function: closed over, it
    # would be compiled into the executable as a constant
    def lm_loss(fn):
        return lambda x, a, b, w, g: jnp.sum(fn(x, w, a, b) * g)

    kern = lambda x, w, a, b: lora_matmul(x, w, a, b, scale=scale,
                                          use_kernel=True)
    orac = lambda x, w, a, b: lora_matmul_ref(x, w, a, b, scale)
    y = jax.jit(kern)(x, w, a, b)
    grads = jax.jit(jax.grad(lm_loss(kern), argnums=(0, 1, 2)))(x, a, b,
                                                                 w, g)
    with jax.default_matmul_precision("highest"):
        y_ref = jax.jit(orac)(x, w, a, b)
        grads_ref = jax.jit(jax.grad(lm_loss(orac), argnums=(0, 1, 2)))(
            x, a, b, w, g)
    errs["lora_matmul"] = rel(y, y_ref)
    errs["lora_matmul_bwd"] = max(rel(u, v) for u, v in zip(grads, grads_ref))

    pool = 4
    xs = nrm((B, d))
    ap, bp = nrm((pool, r, d)) * r ** -0.5, nrm((pool, ff, r)) * 0.1
    idx = jnp.asarray([0, 1, 2, 0][:B] + [3] * max(0, B - 4), jnp.int32)
    got = jax.jit(lambda *t: lora_matmul_gathered(
        *t, scale=scale, use_kernel=True))(xs, w, ap, bp, idx)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *t: lora_matmul_gathered(
            *t, scale=scale, use_kernel=False))(xs, w, ap, bp, idx)
    errs["lora_matmul_gathered"] = rel(got, ref)

    q = nrm((B, 1, H, hd))
    kc, vc = nrm((B, L, H, hd)), nrm((B, L, H, hd))
    lens = jnp.asarray(np.random.default_rng(SEED).integers(1, L + 1, B),
                       jnp.int32)
    got = flash_decode(q, kc, vc, lens, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = flash_decode(q, kc, vc, lens, use_kernel=False)
    errs["flash_decode"] = rel(got, ref)

    MP = L // PS
    NP = B * MP + 1
    kp, vp = nrm((H, NP, PS, hd)), nrm((H, NP, PS, hd))
    bt = jnp.arange(B * MP, dtype=jnp.int32).reshape(B, MP) + 1
    got = paged_decode(q, kp, vp, lens, bt, use_kernel=True)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode(q, kp, vp, lens, bt, use_kernel=False)
    errs["paged_decode"] = rel(got, ref)

    # training attention: the pooled batch of one round step, causal
    Bt, S = TRAIN["clients"] * TRAIN["batch"], TRAIN["seq"]
    qa, ka, va, ga = (jax.random.normal(kk, (Bt, S, H, hd), jnp.float32)
                      for kk in jax.random.split(jax.random.key(SEED + 8), 4))
    pos = jnp.arange(S)
    attn = lambda q, k, v: flash_attention_train(q, k, v, use_kernel=True)
    attn_ref = lambda q, k, v: naive_attention(q, k, v, pos, pos)
    attn_loss = lambda fn: lambda q, k, v, g: jnp.sum(fn(q, k, v) * g)
    got = jax.jit(attn)(qa, ka, va)
    grads = jax.jit(jax.grad(attn_loss(attn), argnums=(0, 1, 2)))(
        qa, ka, va, ga)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(attn_ref)(qa, ka, va)
        grads_ref = jax.jit(jax.grad(attn_loss(attn_ref), argnums=(0, 1, 2)))(
            qa, ka, va, ga)
    errs["flash_attention_train"] = rel(got, ref)
    errs["flash_attention_train_bwd"] = max(rel(u, v) for u, v in
                                            zip(grads, grads_ref))

    for name, e in errs.items():
        print(f"[kernels] {name}: max|kernel - oracle| / max|oracle| = "
              f"{e!r} (tolerance {KERNEL_REL_ERR[name]})")
    for name, e in errs.items():
        check(e <= KERNEL_REL_ERR[name], f"{name} disagrees with its oracle")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded SFL round and its "
                         "one-device reference")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r});"
                 " nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    print(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    cfg = model_config()
    print(f"model: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"(random weights, seed {SEED})")
    t0 = time.perf_counter()
    phases = ([phase_sharded] if args.chips == 4
              else [phase_train, phase_serve, phase_kernels])
    for phase in phases:
        t = time.perf_counter()
        phase(cfg, dev)
        gc.collect()
        print(f"[{phase.__name__}] done in {time.perf_counter() - t:.1f}s "
              f"(smoke timing); {compiles.take()}")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s "
          f"(smoke timing); {peak_bytes(dev)}")
    print(f"memory_stats: {dev.memory_stats()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
