"""Exchange executors: device milliseconds per step of the collective
operations (all-to-all, all-gather, ...) in the traced window, averaged
over the chips.  Moves ``step_ms``."""


def read(ctx):
    steps = ctx.measured.counters["steps"]
    coll, _ = ctx.trace.collective_s()
    return coll * 1e3 / steps if steps else None
