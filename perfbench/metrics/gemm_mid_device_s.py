"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEMM_MID (``jit_GEMM_MID_x<n>``,
``jit_GEMM_MID``): the mid level of the mixed-precision Cholesky, f32
operands in three bf16 passes; mean over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEMM_MID")
