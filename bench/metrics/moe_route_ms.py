"""Device time per SFL round of the MoE layers' routing and combining
(``models/moe.py::apply_moe_dropless``, scopes ``moe.route``: the router,
top-k, sort and gather of the rows; ``moe.combine``: the un-permute and
weighted sum; forward and backward), in ms.

The profiler's events name an instruction but not its scope, so the
path maps them from the round's compiled program (``scope_instructions``
of ``path_train_mla_moe``); a fusion counts where its root operation's
scope is one of the two.  Reads nothing for a run without that map or
where none of its instructions ran in the round's program."""
import trace_reduce as tr

LAYER, MOVES = "model stack", "train_tokens_per_s"


def read(ctx):
    names = set(ctx["run"].get("scope_instructions") or ())
    ev = ctx["events"]
    rounds = len(tr.module_runs(ev, "_train_round_part"))
    if not names or not rounds:
        return None
    ns = sum(d for k, n, _, d, m in ev
             if k == "op" and n in names and "_train_round_part" in m)
    return ns / rounds * 1e-6 if ns else None
