"""Share of the traced training window in which no operation ran on the
device."""
LAYER, MOVES = "device", "train_tokens_per_s"


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"]) if s["window_s"] else None
