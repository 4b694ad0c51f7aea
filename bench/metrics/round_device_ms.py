"""Device time of one execution of the compiled SFL round
(``SflLLM._train_round_part``: I local steps and FedAvg)."""
import trace_reduce as tr

LAYER, MOVES = "SFL round", "train_tokens_per_s"


def read(ctx):
    runs = tr.module_runs(ctx["events"], "_train_round_part")
    return sum(e - s for s, e in runs) / len(runs) * 1e-6 if runs else None
