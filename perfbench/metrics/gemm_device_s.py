"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEMM (``jit_GEMM_x<n>``, ``jit_GEMM``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEMM")
