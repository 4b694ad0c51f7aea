"""Roofline share of the fused LoRA projection kernels in the SFL round
(``kernels/lora_matmul``: forward, dX and the two rank reductions): the
least time the chip could take for their operations and bytes, counted
from shapes (``flops``), over their device time.

The kernels carry no names in the trace: each is a ``closed_call``
operation of the round's program, and nothing else there is.  Every local
step runs, per layer and per adapted projection (q, v), one forward, one
dX and two rank reductions, except layer 0, whose dX nothing needs.  All
of them cover the pooled K x b x S rows (the client side vmapped over its
clients).  The reader counts the calls; where the count is not that, it
cannot tell the kernels apart and reads nothing."""
import flops
import trace_reduce as tr

LAYER, MOVES = "kernels", "train_tokens_per_s"
OP = "closed_call"


def read(ctx):
    cfg, t = ctx["cell"]["config"], ctx["cell"]["traffic"]
    ev = ctx["events"]
    rounds = len(tr.module_runs(ev, "_train_round_part"))
    calls = tr.ops_named(ev, OP, "_train_round_part", exact=True)
    L, d, r = cfg["n_layer"], cfg["n_embd"], cfg["lora_rank"]
    per_step_fwd, per_step_dx, per_step_rr = 2 * L, 2 * (L - 1), 4 * L
    steps = rounds * t["local_steps"]
    if not calls or len(calls) != steps * (per_step_fwd + per_step_dx
                                           + per_step_rr):
        return None
    m = t["clients"] * cfg["batch_size"] * t["seq_len"]
    p = ctx["peaks"]
    least = steps * (per_step_fwd * flops.roofline_s(*flops.lora_fwd(m, d, d, r), p)
                     + per_step_dx * flops.roofline_s(*flops.lora_dx(m, d, d, r), p)
                     + per_step_rr * flops.roofline_s(*flops.rank_reduce(m, r, d), p))
    return 100.0 * least / (sum(dur for _, dur in calls) * 1e-9)
