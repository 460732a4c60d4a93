"""Device time under ``ppo/shuffle`` per PPO update: each epoch's permutation
of the batch and the gather and reshapes into minibatches."""


def read(ctx):
    t = ctx.trace.scope_ns("ppo/shuffle")
    return None if t is None else t / ctx.n_calls / 1e6
