"""Mean device-idle time per gap between executions of the compiled SFL
round that falls in neither ``train.pull`` nor ``train.dispatch``: the
training loop's bookkeeping, its callback, or no span
(``spans.gap_split``).  With the other two it adds up to
``train_round_gap_ms``."""
import spans

LAYER, MOVES = "train entry", "train_tokens_per_s"


def read(ctx):
    split = spans.gap_split(ctx["events"])
    return split["other"] * 1e-6 if split else None
