"""Model FLOP utilization of the whole step: the model FLOPs of the tokens
trained in the traced window (the family's count per token, recompute
excluded) over the window's length times the chips times the chip's bf16
peak.  Moves tokens_per_s."""


def read(run, red):
    w0, w1 = red["window_ns"]
    seconds = (w1 - w0) / 1e9
    flops = run["flops_per_token"] * run["traced_tokens"]
    return 100.0 * flops / (seconds * run["chips"]
                            * run["peak"]["bf16_flops_per_s"])
