"""Device time under ``ppo/logprob`` per PPO update: the factorised
categorical's log-probability, in the rollout and in the loss (forward and
backward)."""


def read(ctx):
    t = ctx.trace.scope_ns("ppo/logprob")
    return None if t is None else t / ctx.n_calls / 1e6
