"""Model FLOP/s utilization of training the DeepSeek-V3 block: the
operations one token needs (forward and LoRA's backward, with the rows
the window's tokens routed to the held experts, from the round's counter;
``flops_mla_moe.train_flops_per_token``) times the traced window's tokens
per second, over the chip's bf16 peak.  Reads nothing for a run without
the counter or a configuration without routed experts."""
import numpy as np

import flops_mla_moe

LAYER, MOVES = "model stack", "train_tokens_per_s"


def read(ctx):
    run, cell = ctx["run"], ctx["cell"]
    cfg = cell["config"]
    rows = run.get("expert_rows")
    if not run["tokens"] or not rows or "n_routed_experts" not in cfg:
        return None
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_token = float(np.sum(rows)) / (run["tokens"] * moe_layers)
    flops = flops_mla_moe.train_flops_per_token(
        cfg, cell["traffic"]["seq_len"], per_token)
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]
