"""Exchange executors: the part of the collective time per step in which no
other operation ran on the chip (what the overlap rung claims to hide).
Moves ``step_ms``."""


def read(ctx):
    steps = ctx.measured.counters["steps"]
    _, exposed = ctx.trace.collective_s()
    return exposed * 1e3 / steps if steps else None
