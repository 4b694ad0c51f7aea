"""The training path: SFL rounds through ``launch.engine.Trainer`` ->
``SflLLM.train_round``, the program's own entry and compiled round.

Set-up builds one trainer from the seed, runs its first global round (the
compile, or the load from the persistent cache) and keeps a host copy of
what that round produced; the same trainer then runs the measured window.
After the window the plain reference replays the first round and the two
are compared (``checks.train``)."""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

import checks
import reference
import spec
import trace_reduce
import weights
from common import jax_key, np_rng


class _WindowClosed(Exception):
    """Raised from the Trainer's per-round callback to end the window."""


def _batches(cell, seed):
    """Endless per-step batches (K, b, S) of random token ids from the
    seed; labels are the next ids, every row distinct."""
    tr, cfg = cell["traffic"], cell["config"]
    K, b, S = tr["clients"], cfg["batch_size"], tr["seq_len"]
    rng = np_rng(seed, 1)
    while True:
        ids = rng.integers(0, cfg["vocab_size"], (K, b, S + 1), np.int32)
        yield {"tokens": ids[..., :S], "labels": ids[..., 1:]}


def first_round_batches(cell, seed):
    """The first round's batches, drawn again from the seed."""
    it = _batches(cell, seed)
    steps = [next(it) for _ in range(cell["traffic"]["local_steps"])]
    return (np.stack([s["tokens"] for s in steps]),
            np.stack([s["labels"] for s in steps]))


def _program(cell, params):
    from repro.configs import TrainConfig
    from repro.core.sfl import SflLLM
    from repro.optim import adamw

    cfg, tr = cell["config"], cell["traffic"]
    arch = spec.arch_for(cfg)
    tc = TrainConfig(num_clients=tr["clients"], batch_size=cfg["batch_size"],
                     local_steps=tr["local_steps"],
                     learning_rate=tr["learning_rate"])
    return SflLLM(arch, params, ell_c=cell["split_layer"], train_cfg=tc,
                  optimizer=adamw(tr["learning_rate"]))


def first_round(cell, seed, callback=None) -> dict:
    """Set-up: weights and adapters from the seed, one trainer around the
    program's compiled round, and its first global round through the
    trainer's own call.  Returns the trainer and its data stream, the
    state after the round, host copies of what the reference compares
    (the round's per-step losses and state, the adapters it started from)
    and the weights."""
    from repro.launch.engine import SflRound, Trainer

    cfg, tr = cell["config"], cell["traffic"]
    params = weights.make_params(cfg, jax_key(seed, 0))
    lora = weights.take(weights.make_loras(cfg, 1, jax_key(seed, 2)), 0)
    sfl = _program(cell, params)
    state0 = sfl.init_state(lora)
    start = jax.device_get((state0.lora_client, state0.lora_server))
    trainer = Trainer(SflRound(sfl, [cfg["batch_size"]] * tr["clients"]),
                      local_steps=tr["local_steps"], callback=callback)
    data = _batches(cell, seed)
    state, hist = trainer.fit(state0, data, global_rounds=1)
    first = {"losses": np.asarray(hist.losses), "state": jax.device_get(state)}
    jax.block_until_ready(state)
    return {"trainer": trainer, "data": data, "state": state, "first": first,
            "start": start, "params": params}


def reference_round(cell, seed, params, start, **kw) -> dict:
    """The plain reference's first round from the same weights, adapters
    and batches; ``kw`` selects the precision, a control or a fault."""
    toks, labels = first_round_batches(cell, seed)
    return reference.sfl_round(cell["config"], params, *start, toks, labels,
                               ell=cell["split_layer"],
                               lr=cell["traffic"]["learning_rate"], **kw)


def run(cell, seed, seconds, trace, log, ctx):
    cfg, tr = cell["config"], cell["traffic"]
    I = tr["local_steps"]
    tokens_per_round = I * tr["clients"] * cfg["batch_size"] * tr["seq_len"]
    rec = {"ends": [], "stop_at": None}

    def on_round(e, state, hist):
        rec["ends"].append(time.perf_counter())
        if rec["stop_at"] is not None and rec["ends"][-1] >= rec["stop_at"]:
            jax.block_until_ready(state)
            rec["ends"][-1] = time.perf_counter()
            raise _WindowClosed

    s = first_round(cell, seed, on_round)
    log.setup_done()

    tracer = trace_reduce.Tracer(ctx["trace_dir"]) if trace else None
    rec["ends"].clear()
    t0 = time.perf_counter()
    rec["stop_at"] = t0 + seconds
    if tracer:
        tracer.start()
    try:
        s["trainer"].fit(s["state"], s["data"], global_rounds=10**9)
    except _WindowClosed:
        pass
    t1 = rec["ends"][-1]
    window = tracer.stop() if tracer else None
    rounds = len(rec["ends"])
    log.window_done()
    out = {"attempted": rounds * I, "failed": 0,
           "rounds": rounds, "tokens": rounds * tokens_per_round,
           "window_s": t1 - t0,
           "e2e": {"train_tokens_per_s": rounds * tokens_per_round
                   / (t1 - t0)}}
    ctx["device_record"]()
    first, start, params = s["first"], s["start"], s["params"]
    del s, rec
    gc.collect()
    if window is not None:
        out["trace"] = window
    t_ref = time.perf_counter()
    ref = reference_round(cell, seed, params, start)
    out["checks"] = checks.train(first, ref, *start, cell["limits"])
    out["notes"] = [f"reference round and comparison "
                    f"{time.perf_counter() - t_ref:.1f}s"]
    return out
