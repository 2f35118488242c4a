"""Decode step, latent attention: device milliseconds per execution of the
decode module (the jitted ``decode_step``) in ops under the ``mla`` scope
(``bench.serve_scopes``).  Moves ``step_ms``."""
from bench.serve_scopes import decode_split


def read(ctx):
    split = decode_split(ctx)
    return None if split is None else split["mla"] * 1e3
