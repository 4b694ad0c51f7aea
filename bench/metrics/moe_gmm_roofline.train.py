"""Roofline share of the held experts' grouped matmuls in the SFL round
(``kernels/grouped_matmul``, megablox kernels): the least time the chip
could take for their operations and bytes, counted from the rows the
round's counter says reached the held experts (``flops_mla_moe``), over
their device time.

Each kernel sits in a named scope that names its instruction:
``moe_gmm_fwd`` (the forward's gate, up and down products, and the
backward's three made again from the kept rows) and ``moe_gmm_dx`` (dX
through down, gate and up).  Every local step runs, in every MoE layer,
6 and 3 of them over the pooled rows of all clients.  The reader reads
nothing unless the counts are that, for as many rounds as the counter
has, and nothing for a run without the counter."""
import flops_mla_moe
import trace_reduce as tr

LAYER, MOVES = "kernels", "train_tokens_per_s"
SCOPES = ("moe_gmm_fwd", "moe_gmm_dx")


def read(ctx):
    cfg, ev = ctx["cell"]["config"], ctx["events"]
    rounds = ctx["run"].get("expert_rows")
    if not rounds or "moe_intermediate_size" not in cfg:
        return None
    if len(tr.module_runs(ev, "_train_round_part")) != len(rounds):
        return None
    least, busy = 0.0, 0.0
    for scope in SCOPES:
        calls = tr.ops_named(ev, scope, "_train_round_part")
        want = [c for r in rounds for step in r for layer in step
                for c in flops_mla_moe.expert_swiglu_calls(sum(layer),
                                                           cfg)[scope]]
        if not calls or len(calls) != len(want):
            return None
        least += sum(flops_mla_moe.roofline_s(o, b, ctx["peaks"])
                     for o, b in want)
        busy += sum(d for _, d in calls) * 1e-9
    return 100.0 * least / busy
