"""Compile: host seconds in XLA compilations and persistent compile-cache
loads, the ``compile_s`` counter of ``repro.comm.telemetry`` (a
``jax.monitoring`` listener on each backend compile).  Read from the
process's telemetry after the check; the warm-up compiles every program
the window runs and the check runs on the host, so this is set-up's.  A
program without the counter gives nothing.  Moves ``setup_s``."""


def read(ctx):
    from repro.comm import telemetry
    return telemetry.stats.snapshot().get("compile_s")
