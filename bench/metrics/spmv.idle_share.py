"""Device: share of the traced window in which no operation ran on the
chip, averaged over the chips, in percent.  Moves ``step_ms``."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
