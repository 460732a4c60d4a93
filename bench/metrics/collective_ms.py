"""Device time of the operations that cross chips per PPO update, averaged
over the chips: ops whose HLO opcode is a collective (``all-reduce``,
``all-gather``, ``all-to-all``, ``reduce-scatter``, ``collective-permute``,
each also as its asynchronous ``-start`` and ``-done`` halves).  On a
four-chip v5e the data-parallel PPO program's trace holds ``all-gather`` and
``all-reduce`` only, both synchronous.  Nothing on one chip, where the
program has none."""

COLLECTIVES = {
    f"{op}{part}"
    for op in ("all-reduce", "all-gather", "all-to-all", "reduce-scatter", "collective-permute")
    for part in ("", "-start", "-done")
}


def read(ctx):
    t = ctx.trace.time_ns(lambda o: o.category in COLLECTIVES)
    return None if t is None else t / ctx.n_calls / 1e6
