"""The training path of the DeepSeek-V3 block (``bench/configs/
moonlight-ep8.json``): SFL rounds through ``launch.engine.Trainer`` ->
``SflLLM.train_round``, the program's own entry and compiled round, as
``path_train`` runs GPT-2, with this block's own weights
(``weights_mla_moe``), plain reference (``reference_mla_moe``) and a
check of the configuration file against the program's ``ArchConfig``.

Set-up builds one trainer from the seed, runs its first global round and
keeps a host copy of what that round produced, the rows routed to each
held expert included; the same trainer then runs the measured window,
whose rounds' row counts the round's callback keeps.  After the window
the plain reference replays the first round and the two are compared:
``checks.train`` and ``route_flip``.

A traced run also maps the round's compiled instructions to the phase
scopes of the MoE layer (``moe.route``, ``moe.combine``): the profiler's
events carry an instruction's name but not its scope, the compiled
program's text carries both.  The round's argument shapes are taken at
set-up's call; after the window the round is lowered and compiled again
from them (a persistent-cache load) and its text read."""
from __future__ import annotations

import gc
import math
import re
import time

import jax
import numpy as np

import checks
import reference_mla_moe
import trace_reduce
import weights_mla_moe
from common import jax_key
from path_train import _batches, _WindowClosed, first_round_batches

ROUTE_SCOPES = ("moe.route", "moe.combine")


def arch_for(cfg: dict):
    """The program's ``ArchConfig`` for the configuration file, cut as the
    file says (depth, experts held, vocabulary), checked against every
    number of the file: a cell runs exactly what its file states."""
    from repro.configs import get_arch

    dep = cfg["deployment"]
    arch = get_arch(cfg["arch"]).replace(
        num_layers=cfg["num_hidden_layers"],
        experts_held=cfg["n_routed_experts"], first_expert=dep["first_expert"],
        vocab_size=cfg["vocab_size"])
    pairs = {
        "hidden_size": arch.d_model,
        "num_attention_heads": arch.num_heads,
        "num_key_value_heads": arch.num_kv_heads,
        "intermediate_size": arch.d_ff,
        "kv_lora_rank": arch.kv_lora_rank,
        "qk_nope_head_dim": arch.qk_nope_head_dim,
        "qk_rope_head_dim": arch.qk_rope_head_dim,
        "v_head_dim": arch.v_head_dim,
        "rope_theta": arch.rope_theta,
        "rms_norm_eps": arch.norm_eps,
        "max_position_embeddings": arch.max_seq_len,
        "first_k_dense_replace": len(arch.prefix),
        "moe_intermediate_size": arch.moe_d_ff,
        "n_shared_experts": arch.shared_d_ff // arch.moe_d_ff,
        "num_experts_per_tok": arch.experts_per_token,
        "routed_scaling_factor": arch.routed_scale,
        "tie_word_embeddings": arch.tie_embeddings,
        "n_routed_experts": arch.held_experts,
        "vocab_size": arch.vocab_size,
        "lora_rank": arch.lora_rank,
        "lora_alpha": arch.lora_alpha,
        "lora_targets": list(arch.lora_targets),
    }
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    kinds = [(p.mixer, p.mlp) for p in arch.layer_kinds]
    want = [("mla", "dense")] * cfg["first_k_dense_replace"] + \
        [("mla", "moe")] * (cfg["num_hidden_layers"]
                            - cfg["first_k_dense_replace"])
    same_kind = (kinds == want and arch.num_experts == dep["router_experts"]
                 and arch.moe_dispatch == "dropless"
                 and arch.mlp_kind == "swiglu" and arch.shared_expert
                 and cfg["hidden_act"] == "silu"
                 and cfg["scoring_func"] == "sigmoid"
                 and cfg["topk_method"] == "noaux_tc"
                 and cfg["norm_topk_prob"] and cfg["q_lora_rank"] is None
                 and cfg["n_group"] == cfg["topk_group"] == 1
                 and arch.shared_d_ff % arch.moe_d_ff == 0)
    if bad or not same_kind:
        raise ValueError(f"config {cfg['arch']}: file and program differ "
                         f"(file, program): {bad}; layer kinds, router and "
                         f"MLP {'agree' if same_kind else 'differ'}")
    return arch


def _program(cell, params):
    from repro.configs import TrainConfig
    from repro.core.sfl import SflLLM
    from repro.optim import adamw

    cfg, tr = cell["config"], cell["traffic"]
    tc = TrainConfig(num_clients=tr["clients"], batch_size=cfg["batch_size"],
                     local_steps=tr["local_steps"],
                     learning_rate=tr["learning_rate"])
    return SflLLM(arch_for(cfg), params, ell_c=cell["split_layer"],
                  train_cfg=tc, optimizer=adamw(tr["learning_rate"]))


def _abstract(x):
    return (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if isinstance(x, jax.Array) else x)


def first_round(cell, seed, callback=None, keep_round_shapes=False) -> dict:
    """Set-up: weights and adapters from the seed, one trainer around the
    program's compiled round, and its first global round through the
    trainer's own call.  Returns the trainer and its data stream, the
    state after the round, host copies of what the reference compares
    (the round's per-step losses, rows per held expert and state, the
    adapters it started from) and the weights; with
    ``keep_round_shapes``, the compiled round and its argument shapes."""
    from repro.launch.engine import SflRound, Trainer

    cfg, tr = cell["config"], cell["traffic"]
    params = weights_mla_moe.make_params(cfg, jax_key(seed, 0))
    lora = weights_mla_moe.make_lora(cfg, jax_key(seed, 2))
    sfl = _program(cell, params)
    state0 = sfl.init_state(lora)
    start = jax.device_get((state0.lora_client, state0.lora_server))
    trainer = Trainer(SflRound(sfl, [cfg["batch_size"]] * tr["clients"]),
                      local_steps=tr["local_steps"], callback=callback)
    data = _batches(cell, seed)
    jitted, shapes = sfl._jit_round_part, []
    if keep_round_shapes:
        def recording(*args):
            shapes.append(jax.tree.map(_abstract, args))
            return jitted(*args)
        sfl._jit_round_part = recording
    try:
        state, hist = trainer.fit(state0, data, global_rounds=1)
    finally:
        sfl._jit_round_part = jitted
    first = {"losses": np.asarray(hist.losses),
             "rows": np.asarray(hist.expert_rows[0]),
             "state": jax.device_get(state)}
    jax.block_until_ready(state)
    out = {"trainer": trainer, "data": data, "state": state, "first": first,
           "start": start, "params": params}
    if keep_round_shapes:
        out["round"] = (jitted, shapes[0])
    return out


def reference_round(cell, seed, params, start, **kw) -> dict:
    """The plain reference's first round from the same weights, adapters
    and batches; ``kw`` selects the precision, a control or a fault."""
    toks, labels = first_round_batches(cell, seed)
    return reference_mla_moe.sfl_round(
        cell["config"], params, *start, toks, labels,
        ell=cell["split_layer"], lr=cell["traffic"]["learning_rate"], **kw)


def route_flip(rows, ref_rows) -> float:
    """The share of the held experts' routed rows that differ between the
    program and the reference, counted per local step, MoE layer and
    expert: sum |rows - ref| / sum ref.  A (token, choice) pair that picks
    another expert counts once or twice (once where one side's expert is
    not held here); a dropped row counts once."""
    rows, ref_rows = (np.asarray(r, np.int64) for r in (rows, ref_rows))
    if rows.shape != ref_rows.shape:
        return math.inf
    return float(np.abs(rows - ref_rows).sum() / max(ref_rows.sum(), 1))


def compare(first: dict, ref: dict, start, limits: dict) -> dict:
    out = checks.train(first, ref, *start, limits)
    out["route_flip"] = {"value": route_flip(first["rows"], ref["rows"]),
                         "limit": float(limits["route_flip"])}
    return out


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def scope_instructions(hlo_text: str, scopes=ROUTE_SCOPES) -> list:
    """Names of the compiled program's instructions whose op name lies in
    one of ``scopes`` (its forward or its transposes)."""
    names = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and any(s in m.group(2) for s in scopes):
            names.add(m.group(1))
    return sorted(names)


def run(cell, seed, seconds, trace, log, ctx):
    cfg, tr = cell["config"], cell["traffic"]
    I = tr["local_steps"]
    tokens_per_round = I * tr["clients"] * cfg["batch_size"] * tr["seq_len"]
    rec = {"ends": [], "rows": [], "stop_at": None}

    def on_round(e, state, hist):
        rec["ends"].append(time.perf_counter())
        rec["rows"].append(hist.expert_rows[-1])
        if rec["stop_at"] is not None and rec["ends"][-1] >= rec["stop_at"]:
            jax.block_until_ready(state)
            rec["ends"][-1] = time.perf_counter()
            raise _WindowClosed

    s = first_round(cell, seed, on_round, keep_round_shapes=trace)
    log.setup_done()

    tracer = trace_reduce.Tracer(ctx["trace_dir"]) if trace else None
    rec["ends"].clear()
    rec["rows"].clear()
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
           "window_s": t1 - t0, "expert_rows": rec["rows"],
           "e2e": {"train_tokens_per_s": rounds * tokens_per_round
                   / (t1 - t0)}}
    ctx["device_record"]()
    per_expert = np.sum(np.asarray(rec["rows"]), axis=(0, 1, 2))
    notes = [f"rows per held expert in the window: {per_expert.tolist()}"]
    if window is not None:
        out["trace"] = window
        t_map = time.perf_counter()
        jitted, shapes = s["round"]
        text = jitted.lower(*shapes).compile().as_text()
        out["scope_instructions"] = names = scope_instructions(text)
        ran = {n for k, n, _, _, m in window["events"]
               if k == "op" and "_train_round_part" in m}
        notes.append(f"round's scopes mapped from its compiled text "
                     f"{time.perf_counter() - t_map:.1f}s: {len(names)} "
                     f"instructions in {'/'.join(ROUTE_SCOPES)}, "
                     f"{len(ran.intersection(names))} of them among the "
                     f"{len(ran)} the round ran")
        del text, jitted, shapes
    first, start, params = s["first"], s["start"], s["params"]
    del s, rec
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_round(cell, seed, params, start)
    out["checks"] = compare(first, ref, start, cell["limits"])
    notes.append(f"reference round and comparison "
                 f"{time.perf_counter() - t_ref:.1f}s")
    out["notes"] = notes
    return out
