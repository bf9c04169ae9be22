"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TSQRT (``jit_TSQRT_x<n>``, ``jit_TSQRT``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TSQRT")
