"""The paper's consumers of ``repro.comm``: the workloads it optimizes.

``DistributedSpMV`` (the paper's sparse matrix-vector product on an EllPack
matrix, and its transpose), ``Heat2D`` (§8 stencil halos) and the CG solver
built on the product.  Planning, the strategy ladder, the §5 models and the
hardware calibration live in ``repro.comm``; nothing there imports this
package.
"""
from repro.core.matrix import EllpackMatrix, make_mesh_like_matrix, spmv_ref_np
from repro.core.spmv import DistributedSpMV
from repro.core.heat2d import Heat2D
from repro.core.solvers import ConjugateGradient, cg_solve

__all__ = [
    "EllpackMatrix", "make_mesh_like_matrix", "spmv_ref_np",
    "DistributedSpMV", "Heat2D", "ConjugateGradient", "cg_solve",
]
