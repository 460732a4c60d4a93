"""Device time under ``env/car_lookup`` per env-step, in the simulation cells:
the per-port lookups of capacity, charge curve and power limit by car model."""


def read(ctx):
    t = ctx.trace.scope_ns("env/car_lookup")
    return None if t is None else t / ctx.env_steps
