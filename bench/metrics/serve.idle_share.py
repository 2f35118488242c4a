"""Device under the engine's host loop: share of the traced window in which
no operation ran on the chip, in percent.  Moves ``tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
