"""Whole model step: model FLOPs of the work done in the window (every
prompt token prefilled and every token decoded, counted by the harness)
over the window times the chip's bf16 peak, in percent.  Moves
``tokens_per_s``."""
from bench.work import mixtral_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    w = ctx.measured.counters["work"]
    flops = mixtral_flops(ctx.config, tokens=w["tokens"],
                          attended=w["attended"], logit_rows=w["logit_rows"])
    window = ctx.trace.window_s
    return 100.0 * flops / (window * ctx.peaks["bf16_flops_per_s"] *
                            ctx.chips)
