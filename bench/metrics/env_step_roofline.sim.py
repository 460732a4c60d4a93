"""The env step's share of its roofline: the least time the chip could take
for one env step (the larger of its bytes over the HBM bandwidth and its
operations over the peak, counted from logical shapes in
``bench/harness/counts.py``) over the measured device time per env-step."""


def read(ctx):
    t = ctx.trace.scope_ns("env/")
    if t is None:
        return None
    least_ns = 1e9 * max(ctx.env_step_bytes / ctx.peak["hbm_bytes_per_s"], ctx.env_step_ops / ctx.peak["flops_per_s"])
    return 100.0 * least_ns / (t / ctx.env_steps)
