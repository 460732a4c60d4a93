"""Self time of the ``wrap/*`` scopes per env-step: device time under a
wrapper that is not under the wrapped env's ``env/*`` stages (AutoReset's
reset and select, VmapWrapper's key splits and reshapes)."""


def read(ctx):
    t = ctx.trace.scope_ns("wrap/", exclude=("env/",))
    return None if t is None else t / ctx.env_steps
