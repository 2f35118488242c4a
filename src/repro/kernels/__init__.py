"""Pallas kernel layer: exchange fast-path kernels plus the compute
hot-spots the paper's consumers use.

The canonical entry points are the wrappers in ``repro.kernels.ops``
(blocking/padding/window planning lives there);
they are re-exported here so consumers stop reaching into submodules.
This package never imports ``repro.comm`` — the comm layer depends on it,
not the other way around.
"""
from repro.kernels.ops import (
    accumulate_into,
    accumulate_segments,
    decode_attention,
    ellpack_spmv,
    make_spmv_on_copy_sharded,
    make_spmv_overlap_sharded,
    pack_gather,
    plan_spmv_windows,
    selective_scan,
    stencil2d,
    unpack_dest,
    unpack_scatter_set,
)

__all__ = [
    "plan_spmv_windows", "ellpack_spmv",
    "make_spmv_on_copy_sharded", "make_spmv_overlap_sharded",
    "pack_gather", "unpack_dest", "unpack_scatter_set",
    "accumulate_segments", "accumulate_into",
    "stencil2d", "decode_attention", "selective_scan",
]
