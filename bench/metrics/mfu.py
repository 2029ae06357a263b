"""Model FLOP utilization (%) of whole GRPO iterations: the model FLOPs
the window's iterations require (rollout prefill and decode, the
inference pass, the actor's forward and backward, counted from the
configuration's shapes), over window seconds x chips x the chip's bf16
peak."""


def read(ctx):
    if not ctx["iterations"] or ctx["window_s"] <= 0:
        return None
    flops = ctx["iteration_flops"] * ctx["iterations"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / (ctx["window_s"] * peak)
