"""Roofline share of the training attention kernels at latent
attention's head widths (``kernels/flash_attention``: q and k heads of
qk_nope + qk_rope dims, v heads of v_head_dim): the least time the chip
could take for their operations and bytes (``flops_mla_moe.
attention_calls``), over their device time.

Every local step runs, in each of the L layers (the client's leading
layer vmapped over its clients into one call, the server's in its depth
scan), one forward (``attn_fwd``) and one backward over the K x b
sequences: ``attn_bwd`` where the sequence is one kernel block each way,
else ``attn_dkv`` and ``attn_dq``.  The reader reads nothing unless the
counts are that, and nothing for a configuration without latent
attention."""
import flops_mla_moe
import trace_reduce as tr

LAYER, MOVES = "kernels", "train_tokens_per_s"
BACKWARDS = (("attn_bwd",), ("attn_dkv", "attn_dq"))


def read(ctx):
    cfg, t, ev = ctx["cell"]["config"], ctx["cell"]["traffic"], ctx["events"]
    if "qk_rope_head_dim" not in cfg:
        return None
    rounds = len(tr.module_runs(ev, "_train_round_part"))
    want = rounds * t["local_steps"] * cfg["num_hidden_layers"]
    calls = {k: tr.ops_named(ev, k, "_train_round_part")
             for k in ("attn_fwd",) + sum(BACKWARDS, ())}
    ran = sorted(k for k, c in calls.items() if c)
    if not rounds or not any(ran == sorted(("attn_fwd",) + b)
                             for b in BACKWARDS):
        return None
    if any(len(calls[k]) != want for k in ran):
        return None
    fwd, bwd = flops_mla_moe.attention_calls(
        t["clients"] * cfg["batch_size"], t["seq_len"], cfg)
    p = ctx["peaks"]
    least = want * (flops_mla_moe.roofline_s(*fwd, p)
                    + flops_mla_moe.roofline_s(*bwd, p))
    return 100.0 * least / (sum(d for k in ran for _, d in calls[k]) * 1e-9)
