"""Device time under ``env/constraint_scale`` per env-step, in the PPO cells:
the Eq. 5 scale of the step's currents (each constraint's load, each port's
least scale over the constraints holding it, the rescale)."""


def read(ctx):
    t = ctx.trace.scope_ns("env/constraint_scale")
    return None if t is None else t / ctx.env_steps
