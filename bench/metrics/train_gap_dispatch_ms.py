"""Mean device-idle time per gap between executions of the compiled SFL
round that falls inside ``Trainer.fit``'s ``train.dispatch`` span: the
next round's batches and mask to the device and its enqueue
(``spans.gap_split``)."""
import spans

LAYER, MOVES = "train entry", "train_tokens_per_s"


def read(ctx):
    split = spans.gap_split(ctx["events"])
    return split["dispatch"] * 1e-6 if split else None
