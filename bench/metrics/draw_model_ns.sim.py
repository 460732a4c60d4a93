"""Device time under ``env/draw_model`` per env-step, in the simulation cells:
the car-model draw of every port (the fleet mix's CDF computed once per
env, then each port's uniform compared with it and the entries below
counted)."""


def read(ctx):
    t = ctx.trace.scope_ns("env/draw_model")
    return None if t is None else t / ctx.env_steps
