"""Model step, decode: device milliseconds per execution of the decode
program (XLA module of the jitted ``decode_step``) in the traced window.
Moves ``itl_p95_ms``."""


def read(ctx):
    secs, count = ctx.trace.module_time("decode_step")
    return secs * 1e3 / count if count else None
