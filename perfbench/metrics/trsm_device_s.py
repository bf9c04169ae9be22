"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRSM (``jit_TRSM_x<n>``, ``jit_TRSM``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRSM")
