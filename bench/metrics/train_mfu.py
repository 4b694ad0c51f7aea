"""Model FLOP/s utilization of training: the operations one token needs
(forward and LoRA's backward, ``flops.train_flops_per_token``) times the
traced window's tokens per second, over the chip's bf16 peak."""
import flops

LAYER, MOVES = "model stack", "train_tokens_per_s"


def read(ctx):
    run, cfg = ctx["run"], ctx["cell"]["config"]
    if not run["tokens"]:
        return None
    per_tok = flops.train_flops_per_token(cfg, ctx["cell"]["traffic"]["seq_len"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * per_tok * rate / ctx["peaks"]["bf16_flops_per_s"]
