"""Serving driver: continuous-batching engine over the (optionally
LoRA-adapted) model — fused in-graph decode with a paged KV cache and
chunked prefill by default (``--slab`` forces the fixed-slab layout).
CPU demo:

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-s --reduced \
      --requests 12 --slots 4 --gen 16
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-s")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--lora-checkpoint", default="")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--slab", action="store_true",
                    help="fixed-slab KV cache instead of the paged pool")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page pool size (0 = slab-equivalent capacity); "
                         "shrink to oversubscribe slots against HBM")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request decode-step residency budget; a "
                         "request over budget is preempted in-graph and "
                         "requeued for prefix recompute (0 = no deadline; "
                         "paged engine only)")
    ap.add_argument("--preempt", action="store_true",
                    help="under page pressure, evict the lowest-priority "
                         "resident instead of queueing new work "
                         "(paged engine only)")
    ap.add_argument("--adapters", type=int, default=0,
                    help="serve N distinct tenant adapters from ONE engine "
                         "(multi-tenant; paged engine only; 0 = single "
                         "shared adapter)")
    ap.add_argument("--adapter-pool", type=int, default=0,
                    help="device-resident adapter slots (0 = auto: enough "
                         "for the batch, capped at 8 so cold tenants "
                         "exercise LRU paging)")
    ap.add_argument("--tenant-trace", choices=["roundrobin", "zipf"],
                    default="roundrobin",
                    help="how requests map to tenants: uniform round-robin "
                         "or a Zipf-skewed popularity mix")
    ap.add_argument("--tenant-quota", type=int, default=0,
                    help="max live slots per tenant (0 = unlimited)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np

    from ..configs import get_arch
    from ..models import init_lora_stack, init_params
    from ..models.generate import SampleConfig
    from ..serving import AdapterRegistry, Request, ServingEngine
    from .compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=max(4, len(cfg.pattern)))

    key = jax.random.key(args.seed)
    params = init_params(cfg, key)
    registry = None
    if args.adapters:
        # one trained adapter per tenant (federated fleets emit these);
        # the pool holds a bounded working set and LRU-pages the rest
        pool = args.adapter_pool or max(args.slots,
                                        min(args.adapters, 8))
        registry = AdapterRegistry(cfg, pool_size=pool, rank=args.rank)
        for t in range(args.adapters):
            registry.publish(t, init_lora_stack(
                cfg, jax.random.key(args.seed + 1 + t), args.rank))
        lora = None
    else:
        lora = init_lora_stack(cfg, jax.random.key(args.seed + 1), args.rank)
        if args.lora_checkpoint:
            from ..checkpoint import restore_pytree
            lora = restore_pytree(args.lora_checkpoint, lora)

    sc = (SampleConfig(greedy=True) if args.temperature == 0.0
          else SampleConfig(temperature=args.temperature))
    paged = False if args.slab else None    # None = auto
    eng = ServingEngine(cfg, params, lora=lora, adapters=registry,
                        tenant_quota=args.tenant_quota,
                        max_slots=args.slots,
                        max_len=args.max_len, sc=sc, seed=args.seed,
                        paged=paged,
                        page_size=args.page_size,
                        num_pages=args.num_pages or None,
                        preempt=args.preempt)
    if (args.deadline_steps or args.preempt) and not eng.paged:
        raise SystemExit("--deadline-steps/--preempt need the paged engine "
                         "(drop --slab)")

    rng = np.random.default_rng(args.seed)

    def tenant_of(i: int) -> int:
        if not args.adapters:
            return 0
        if args.tenant_trace == "zipf":
            return int(rng.zipf(1.5)) % args.adapters
        return i % args.adapters

    reqs = [Request(uid=i,
                    prompt=rng.integers(5, cfg.vocab_size,
                                        rng.integers(4, args.prompt_len + 1)
                                        ).tolist(),
                    max_new_tokens=args.gen,
                    deadline_steps=args.deadline_steps or None,
                    tenant=tenant_of(i))
            for i in range(args.requests)]
    for r in reqs:
        eng.submit(r)

    t0 = time.time()
    steps = 0
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
    wall = time.time() - t0
    total = sum(len(r.output) for r in reqs)
    mode = ("slab" if not eng.paged else
            f"paged(ps={eng.page_size},np={eng.num_pages})")
    print(f"served {len(reqs)} requests / {total} tokens in {wall:.2f}s "
          f"({total / wall:.1f} tok/s) with {args.slots} slots, "
          f"{steps} engine steps, {eng.prefill_compiles()} prefill "
          f"compiles ({mode} engine)")
    if eng.paged and (args.deadline_steps or args.preempt):
        print(f"fault stats: {eng.stats['preemptions']} preemptions "
              f"({eng.stats['deadline_preemptions']} deadline), "
              f"{eng.stats['recomputed_tokens']} tokens recomputed, "
              f"{eng.stats['quarantined']} quarantined")
    if registry is not None:
        tt = eng.stats["tenant_tokens"]
        dist = " ".join(f"t{t}:{tt[t]}" for t in sorted(tt))
        print(f"multi-tenant: {args.adapters} tenants over "
              f"{registry.pool_size} pool slots ({args.tenant_trace} trace), "
              f"{eng.stats['adapter_swaps']} adapter swaps "
              f"({registry.stats['evictions']} evictions, "
              f"{registry.stats['hot_swaps']} hot swaps)")
        print(f"per-tenant tokens: {dist}")
    print("sample token ids:", reqs[0].output[:12])


if __name__ == "__main__":
    main()
