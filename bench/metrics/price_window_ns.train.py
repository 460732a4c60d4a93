"""Device time under ``env/price_window`` per env-step, in the PPO cells: the
price-horizon gather of the observation."""


def read(ctx):
    t = ctx.trace.scope_ns("env/price_window")
    return None if t is None else t / ctx.env_steps
