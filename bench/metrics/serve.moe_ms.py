"""Decode step, MoE FFN: device milliseconds per execution of the decode
module (the jitted ``decode_step``) in ops under a ``moe.*`` scope, the
exchange's ``comm.*`` scopes inside them included (``bench.serve_scopes``).
Moves ``step_ms``."""
from bench.serve_scopes import decode_split


def read(ctx):
    split = decode_split(ctx)
    return None if split is None else split["moe"] * 1e3
