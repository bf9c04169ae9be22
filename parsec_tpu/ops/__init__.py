"""Tile kernels (XLA/Pallas executables for task BODYs) and tile
algorithms (dpotrf, dgeqrf, dgetrf_nopiv, dgetrf_1d, pdgemm,
pdgemm_dtd) and the 1D stencil mini-app (stencil_1d)."""
from .linalg import (axpy, gemm, gemm_nn, gemm_nn_sub, gemm_nt, gemm_nt_lo,
                     gemm_nt_mid,
                     gemm_tn, gemm_tn_sub, geqrt, geqrt_r, getrf_1d_laswp,
                     getrf_1d_panel, getrf_1d_update, getrf_nopiv, lauum_lower,
                     potrf, scal, stencil_ghosts, stencil_tile, syrk_ln,
                     syrk_lt, transpose,
                     trmm_lower_trans, trsm_lower, trsm_lower_right_neg,
                     trsm_lower_trans, trsm_lower_unit, trsm_panel,
                     trsm_panel_mid,
                     trsm_upper_right, trtri_lower, tsmqr, tsqrt, tsqrt_r,
                     unmqr)
from . import dpotrf as dpotrf_module
from .dpotrf import dpotrf, dpotrf_factory, dpotrf_taskpool, make_spd
from .dpotrf_dtd import dpotrf_dtd
from .dpotrf_mp import dpotrf_mp, dpotrf_mp_taskpool
from .dgeqrf import dgeqrf, dgeqrf_factory, dgeqrf_taskpool
from .inverse import dgesv, dgetrs
from .dpoinv import (dlauum, dlauum_taskpool, dpoinv, dpotri, dtrtri,
                     dtrtri_taskpool)
from .dgetrf import (dgetrf, dgetrf_factory, dgetrf_nopiv, dgetrf_nopiv_taskpool,
                     make_diag_dominant)
from .dgetrf_1d import dgetrf_1d, dgetrf_1d_factory, dgetrf_1d_taskpool
from .pdgemm import pdgemm, pdgemm_factory, pdgemm_taskpool
from .pdgemm_dtd import pdgemm_dtd
from .dtrsm import (dposv, dtrsm_lower_taskpool, dtrsm_lower_trans_taskpool)
from .stencil_1d import stencil_1d, stencil_1d_taskpool, stencil_weights

from . import pallas_kernels
from .pallas_kernels import flash_attention

__all__ = ["potrf", "trsm_panel", "syrk_ln", "gemm_nt", "gemm_nn",
           "gemm_nn_sub", "gemm", "axpy", "scal", "transpose",
           "geqrt", "geqrt_r", "unmqr", "tsqrt", "tsqrt_r", "tsmqr",
           "getrf_nopiv", "trsm_lower_unit", "trsm_upper_right",
           "getrf_1d_panel", "getrf_1d_update", "getrf_1d_laswp",
           "dpotrf", "dpotrf_factory", "dpotrf_taskpool", "make_spd",
           "dpotrf_dtd", "dpotrf_mp", "dpotrf_mp_taskpool",
           "gemm_nt_mid", "gemm_nt_lo", "trsm_panel_mid",
           "dgeqrf", "dgeqrf_factory", "dgeqrf_taskpool",
           "dgetrf", "dgetrf_nopiv", "dgetrf_nopiv_taskpool", "dgetrf_factory",
           "dgetrf_1d", "dgetrf_1d_factory", "dgetrf_1d_taskpool",
           "dpoinv", "dpotri", "dtrtri", "dlauum", "dtrtri_taskpool",
           "dlauum_taskpool", "trtri_lower", "trsm_lower_right_neg",
           "trmm_lower_trans", "lauum_lower", "syrk_lt", "gemm_tn",
           "dgetrs", "dgesv",
           "make_diag_dominant",
           "pdgemm", "pdgemm_factory", "pdgemm_taskpool", "pdgemm_dtd",
           "dposv", "dtrsm_lower_taskpool", "dtrsm_lower_trans_taskpool",
           "trsm_lower", "trsm_lower_trans", "gemm_tn_sub",
           "stencil_1d", "stencil_1d_taskpool", "stencil_weights",
           "stencil_tile", "stencil_ghosts",
           "pallas_kernels", "flash_attention"]
