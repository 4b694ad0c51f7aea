"""Device time per SFL round of the fused training attention kernels
(``kernels/flash_attention``: the forward, and the backward as one kernel
or as a dK/dV and a dQ kernel), in ms.

Each kernel sits in a named scope of its own, so its operation in the
round's program carries the scope's name (``attn_fwd``, ``attn_bwd``,
``attn_dkv``, ``attn_dq``, wrapped by the transformations around it:
``jvp_attn_fwd_``, ``transpose_jvp_attn_bwd__``).  Every local step runs,
in every one of the L attention layers (the client's layer 0 vmapped over
its clients into one call, the server's layers in its depth scan), one
forward and one backward: ``attn_bwd`` where the sequence is one kernel
block each way (S = 512 in both cells), else ``attn_dkv`` and
``attn_dq``.  So the forward and each backward name count rounds x I x L
operations.  The reader reads nothing unless the counts are that: a
program without the kernels (the jnp attention path) or one that runs
them elsewhere."""
import trace_reduce as tr

LAYER, MOVES = "kernels", "train_tokens_per_s"
BACKWARDS = (("attn_bwd",), ("attn_dkv", "attn_dq"))


def read(ctx):
    ev = ctx["events"]
    rounds = len(tr.module_runs(ev, "_train_round_part"))
    want = rounds * ctx["cell"]["traffic"]["local_steps"] * \
        ctx["cell"]["config"]["n_layer"]
    calls = {k: tr.ops_named(ev, k, "_train_round_part")
             for k in ("attn_fwd",) + sum(BACKWARDS, ())}
    ran = [k for k, c in calls.items() if c]
    if not rounds or not any(sorted(ran) == sorted(("attn_fwd",) + b)
                             for b in BACKWARDS):
        return None
    if any(len(calls[k]) != want for k in ran):
        return None
    return sum(d for k in ran for _, d in calls[k]) / rounds * 1e-6
