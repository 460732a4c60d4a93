"""Whole PPO step's share of the chips' peak: the MLP's FLOPs per update
(``bench/harness/counts.py``) times updates per second of the traced
window, over chips times the published peak."""


def read(ctx):
    if not ctx.flops_per_call:
        return None
    rate = ctx.flops_per_call * ctx.n_calls / (ctx.trace.window_ns * 1e-9)
    return 100.0 * rate / (len(ctx.trace.chips) * ctx.peak["flops_per_s"])
