"""Model step, prefill: device milliseconds per execution of the prefill
program (XLA module of the jitted ``prefill``, one 128-token chunk) in the
traced window.  Moves ``ttft_p95_ms``."""


def read(ctx):
    secs, count = ctx.trace.module_time("prefill")
    return secs * 1e3 / count if count else None
