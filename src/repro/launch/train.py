"""SflLLM training driver — argument parsing over launch.engine.Trainer.

Two modes:
  * ``--mode sfl`` (default): the paper's Algorithm 1 — K clients + main
    server + federated server (core.sfl), one jitted call per global round
    (scan over the I local steps + in-graph FedAvg), with the resource
    allocator picking split/rank and the engine reporting the modeled
    wireless wall clock of every round.  With multiple devices the client
    axis is sharded over a ("clients",) mesh.
  * ``--mode pod``: the datacenter lowering — one jit-compiled LoRA train
    step sharded over an N-device ("data", "model") mesh, scanned I times
    per round.

Example (CPU, ~1 min):
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-s --reduced \
      --steps 24 --mode sfl
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-s")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--mode", choices=["sfl", "pod"], default="sfl")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=4e-4)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--split", type=int, default=0, help="0 = allocator picks")
    ap.add_argument("--local-steps", type=int, default=6)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=1, help="rounds")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> None:
    args = build_argparser().parse_args()

    from ..configs import DEFAULT_SYSTEM, TrainConfig, get_arch
    from ..core import (Problem, bcd_minimize_delay, latency_report,
                        sample_clients)
    from ..core.sfl import SflLLM
    from ..data import WordTokenizer, e2e_splits, iid_partition, sfl_batches
    from ..models import init_lora_stack, init_params
    from ..optim import adamw
    from .compile_cache import enable_compile_cache
    from .engine import PodRound, SflRound, Trainer
    from .mesh import make_auto_mesh, make_client_mesh

    print(f"compile cache: {enable_compile_cache()}")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=max(4, len(cfg.pattern)))
    cfg = cfg.replace(lora_rank=args.rank)

    # data ------------------------------------------------------------------
    train, val, _ = e2e_splits(4000, 400, 400, seed=args.seed)
    tok = WordTokenizer.from_corpus([e.text for e in train])
    cfg = cfg.replace(vocab_size=max(cfg.vocab_size, tok.vocab_size)) \
        if tok.vocab_size > cfg.vocab_size else cfg
    parts = [np.array(train, dtype=object)[idx]
             for idx in iid_partition(len(train), args.clients, args.seed)]
    data = sfl_batches(tok, parts, args.batch, args.seq, args.seed)

    key = jax.random.key(args.seed)
    params = init_params(cfg, key)
    lora = init_lora_stack(cfg, jax.random.key(args.seed + 1), args.rank)
    tc = TrainConfig(num_clients=args.clients, batch_size=args.batch,
                     local_steps=args.local_steps, learning_rate=args.lr)
    rounds = max(1, args.steps // args.local_steps)

    # resource allocation (paper Algorithm 3) picks split + validates rank --
    envs = tuple(sample_clients(DEFAULT_SYSTEM, args.seed))
    prob = Problem(cfg=cfg, sys_cfg=DEFAULT_SYSTEM, envs=envs,
                   seq_len=args.seq, batch=args.batch,
                   local_steps=args.local_steps,
                   rank_candidates=(args.rank,))
    alloc, hist = bcd_minimize_delay(prob, rank0=args.rank)
    ell_c = args.split or alloc.ell_c
    print(f"allocator: split={alloc.ell_c} rank={alloc.rank} "
          f"modeled total delay {hist[-1]:.1f}s (using split={ell_c})")

    if args.mode == "sfl":
        # client-axis data parallelism over every visible device; K must
        # split evenly, or the round would silently run on one device
        devs = jax.devices()
        n_dev = len(devs)
        if n_dev > 1 and args.clients % n_dev:
            raise SystemExit(
                f"--clients {args.clients} is not a multiple of the "
                f"{n_dev} visible devices; pass a multiple of {n_dev}")
        mesh = make_client_mesh() if n_dev > 1 else None
        print(f"round runs on {n_dev} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})"
              + (f", client axis sharded {args.clients // n_dev} per device"
                 if mesh is not None else ""))
        sfl = SflLLM(cfg, params, ell_c=ell_c, train_cfg=tc,
                     optimizer=adamw(args.lr), mesh=mesh)
        state = sfl.init_state(lora)
        report = latency_report(
            cfg, DEFAULT_SYSTEM, envs, alloc.rates_main(DEFAULT_SYSTEM, envs),
            alloc.rates_fed(DEFAULT_SYSTEM, envs), ell_c, alloc.rank,
            args.seq, args.batch, args.local_steps, rounds)
        algo = SflRound(sfl, [len(p) for p in parts])
    else:
        n = len(jax.devices())
        mesh = make_auto_mesh((n, 1), ("data", "model"))
        algo = PodRound(cfg, params, None,      # None -> fast train defaults
                        adamw(args.lr), mesh)
        state = algo.init_state(lora)
        report = None

        pooled = data
        def _pool(it=pooled):
            for kb in it:
                yield {"tokens": kb["tokens"].reshape(-1, args.seq),
                       "labels": kb["labels"].reshape(-1, args.seq)}
        data = _pool()

    trainer = Trainer(algo, local_steps=args.local_steps,
                      log_every=args.log_every, round_latency=report,
                      checkpoint_path=args.checkpoint)
    state, hist = trainer.fit(state, data, global_rounds=rounds)
    msg = (f"{len(hist.losses)} steps in {hist.wall_seconds:.1f}s "
           f"({hist.steps_per_sec:.2f} steps/s); "
           f"loss {hist.losses[0]:.3f} -> {hist.losses[-1]:.3f}")
    if hist.modeled_seconds:
        msg += f"; modeled wireless wall clock {hist.modeled_seconds:.1f}s"
    print(msg)
    if args.checkpoint:
        print("saved", args.checkpoint)


if __name__ == "__main__":
    main()
