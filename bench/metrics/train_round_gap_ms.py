"""Mean device-idle time between consecutive executions of the compiled
SFL round: what the Trainer's host loop (data staging, loss pull, dispatch)
leaves the chip waiting."""
import trace_reduce as tr

LAYER, MOVES = "train entry", "train_tokens_per_s"


def read(ctx):
    runs = tr.module_runs(ctx["events"], "_train_round_part")
    lo, hi = tr.window_bounds(ctx["events"])
    gaps = tr.gaps_between(ctx["events"], runs, lo, hi)
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
