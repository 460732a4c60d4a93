"""Device time under the ``ppo/update`` scope (the minibatch epochs) per PPO update."""


def read(ctx):
    t = ctx.trace.scope_ns("ppo/update")
    return None if t is None else t / ctx.n_calls / 1e6
