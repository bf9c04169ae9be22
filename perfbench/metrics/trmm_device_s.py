"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRMM (``jit_TRMM_x<n>``, ``jit_TRMM``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRMM")
