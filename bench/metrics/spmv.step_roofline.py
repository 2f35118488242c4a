"""Local compute: the least time of one product per chip (the least HBM
bytes of y = D x + A x over the chip's rows, over the chip's bandwidth) as a
share of the device time per execution of the product program (the XLA
module of ``step_local``) in which an operation other than a collective ran,
in percent.  That time holds the local product and the on-chip pack and
unpack, and leaves out the exchange's collectives, the rescale program and
the host's gaps.  It counts the same work whatever rung computes it.  Moves
``step_ms``."""
from bench.work import spmv_least_bytes


def read(ctx):
    c = ctx.measured.counters
    secs, count = ctx.trace.module_compute_s("step_local")
    if ctx.peaks is None or not count or secs <= 0:
        return None
    least = spmv_least_bytes(c["n"] // c["p"], c["r_nz"]) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / count)
