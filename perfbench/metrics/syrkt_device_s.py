"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class SYRKT (``jit_SYRKT_x<n>``, ``jit_SYRKT``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "SYRKT")
