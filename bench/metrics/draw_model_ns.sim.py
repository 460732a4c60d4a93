"""Device time under ``env/draw_model`` per env-step, in the simulation cells:
the car-model draw of every port (``jax.random.choice`` over the fleet mix)."""


def read(ctx):
    t = ctx.trace.scope_ns("env/draw_model")
    return None if t is None else t / ctx.env_steps
