"""Mean device-idle time per gap between executions of the compiled SFL
round that falls inside ``Trainer.fit``'s ``train.pull`` span: the host
fetching the losses and the rollback flag of the round that just ended
(``spans.gap_split``)."""
import spans

LAYER, MOVES = "train entry", "train_tokens_per_s"


def read(ctx):
    split = spans.gap_split(ctx["events"])
    return split["pull"] * 1e-6 if split else None
