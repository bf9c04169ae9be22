"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRSM_MID (``jit_TRSM_MID_x<n>``,
``jit_TRSM_MID``): the triangular solves of every tile outside the hi
band of the mixed-precision Cholesky, their products in three bf16
passes; mean over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRSM_MID")
