"""MoE FFN: the least HBM time of a decode tick's MoE layers (the routed
experts that received a token, mean over the checked ticks, the shared
experts and the router, ``bench.serve_work.moe_least_bytes``) as a share
of ``serve.moe_ms``, in percent.  Moves ``step_ms``."""
from bench.serve_scopes import decode_split
from bench.serve_work import moe_least_bytes


def read(ctx):
    split = decode_split(ctx)
    if split is None or ctx.peaks is None or split["moe"] <= 0:
        return None
    least = moe_least_bytes(ctx.config, ctx.measured.counters[
        "experts_hit"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / split["moe"]
