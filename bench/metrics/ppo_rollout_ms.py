"""Device time under the ``ppo/rollout`` scope per PPO update."""


def read(ctx):
    t = ctx.trace.scope_ns("ppo/rollout")
    return None if t is None else t / ctx.n_calls / 1e6
