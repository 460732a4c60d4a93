"""Device time under any ``env/*`` scope per env-step, in the PPO cells."""


def read(ctx):
    t = ctx.trace.scope_ns("env/")
    return None if t is None else t / ctx.env_steps
