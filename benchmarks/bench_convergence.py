"""Paper Figs. 3-4: validation-loss curves per LoRA rank + steps needed to
reach a target loss, on the synthetic E2E task with a reduced GPT-2.

Also fits the E(r) convergence model (core.convergence) from the measured
(rank, steps) pairs — the calibration the paper performs offline for P4.

Training goes through launch.engine (one compiled scan per round).
"""
from __future__ import annotations

import time

import jax

from repro.configs import TrainConfig, get_arch
from repro.core.convergence import fit_convergence_model
from repro.core.sfl import CentralizedLoRA
from repro.data import WordTokenizer, batches, e2e_splits
from repro.launch.engine import CentralizedRound, Trainer
from repro import models as M
from repro.optim import adamw

RANKS = (1, 2, 4, 8)
STEPS = 120
EVAL_EVERY = 12           # paper: validation every 12 steps
B, S = 8, 48


def run(seed: int = 0):
    cfg = get_arch("gpt2-s").reduced(num_layers=4)
    train, val, _ = e2e_splits(2000, 200, 200, seed=seed)
    tok = WordTokenizer.from_corpus([e.text for e in train])
    key = jax.random.key(seed)
    params = M.init_params(cfg, key)
    tc = TrainConfig(batch_size=B)

    val_iter = batches(tok, val, 32, S, rng=123)
    val_batch = next(val_iter)

    from repro.models.model import loss_fn
    eval_loss = jax.jit(lambda l, bt: loss_fn(
        cfg, params, l, bt, rt=M.Runtime(attn_impl="naive"))[1]["loss"])

    curves = {}
    for rank in RANKS:
        lora = M.init_lora_stack(cfg, jax.random.key(seed + 1), rank=rank)
        cen = CentralizedLoRA(cfg, params, tc, adamw(4e-3))
        state = cen.init_state(lora)
        data = batches(tok, train, B, S, rng=seed)
        losses = []

        def on_round(e, st, h, losses=losses):
            losses.append(float(eval_loss(st[0], val_batch)))

        trainer = Trainer(CentralizedRound(cen), local_steps=EVAL_EVERY,
                          callback=on_round)
        t0 = time.time()
        state, _ = trainer.fit(state, data,
                               global_rounds=STEPS // EVAL_EVERY)
        curves[rank] = (losses, time.time() - t0)
    return curves


def steps_to_target(curves, target=None):
    finals = [c[0][-1] for c in curves.values()]
    target = target if target is not None else max(finals) * 1.02
    out = {}
    for rank, (losses, _) in curves.items():
        idx = next((i for i, l in enumerate(losses) if l <= target),
                   len(losses) - 1)
        out[rank] = (idx + 1) * EVAL_EVERY
    return target, out


def main(emit):
    curves = run()
    target, s2t = steps_to_target(curves)
    for rank, (losses, wall) in curves.items():
        emit(f"fig3/loss_curve_rank{rank}",
             wall / STEPS * 1e6,
             "curve=" + "|".join(f"{l:.4f}" for l in losses))
    for rank, steps in s2t.items():
        emit(f"fig4/steps_to_loss_{target:.3f}_rank{rank}", 0.0,
             f"steps={steps}")
    model = fit_convergence_model(list(s2t), [s2t[r] for r in s2t])
    emit("fig4/E_r_fit", 0.0,
         f"e_inf={model.e_inf:.2f};c={model.c:.2f};alpha={model.alpha:.2f}")


if __name__ == "__main__":
    main(lambda n, t, d: print(f"{n},{t},{d}"))
