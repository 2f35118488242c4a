"""Plan acquisition: host seconds the program spent loading plan-cache
entries in set-up (disk read, inflate and deserialise), the ``plan.load``
span of ``repro.comm.telemetry``, summed over the entries loaded; 0 when
every plan was built.  Read from the process's telemetry, which only set-up
feeds with plan loads; a program without telemetry spans gives nothing.
Moves ``setup_s``."""


def read(ctx):
    from repro.comm import telemetry
    spans = telemetry.stats.snapshot().get("spans")
    if spans is None:
        return None
    return spans.get("plan.load", {}).get("seconds", 0.0)
