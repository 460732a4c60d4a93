"""Device time of the MLP inside the minibatch epochs per PPO update: ops
under both ``ppo/update`` and ``ppo/mlp``, forward and backward (the backward
ops sit under ``transpose(jvp(ppo/mlp))``)."""
from bench.harness.trace import in_scope


def read(ctx):
    t = ctx.trace.time_ns(lambda o: in_scope(o.scope, "ppo/update") and in_scope(o.scope, "ppo/mlp"))
    return None if t is None else t / ctx.n_calls / 1e6
