"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEMM_LO (``jit_GEMM_LO_x<n>``,
``jit_GEMM_LO``): the lo level of the mixed-precision Cholesky, bf16
operands in one pass; mean over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEMM_LO")
