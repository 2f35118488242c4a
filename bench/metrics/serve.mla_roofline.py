"""Latent attention: the least HBM time of a decode tick's MLA (the latent
rows the lanes attend, mean over the window's ticks, and each layer's MLA
weights, ``bench.serve_work.mla_least_bytes``) as a share of
``serve.mla_ms``, in percent.  Moves ``step_ms``."""
from bench.serve_scopes import decode_split
from bench.serve_work import mla_least_bytes


def read(ctx):
    split = decode_split(ctx)
    if split is None or ctx.peaks is None or split["mla"] <= 0:
        return None
    least = mla_least_bytes(ctx.config, ctx.measured.counters[
        "attended_rows"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / split["mla"]
