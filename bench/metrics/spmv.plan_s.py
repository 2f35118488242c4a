"""Plan acquisition: host seconds of the harness span around
``DistributedSpMV(...)`` (plan build or plan-cache load, strategy
resolution, device placement).  Moves ``setup_s``."""


def read(ctx):
    return ctx.spans.total("setup.engine")
