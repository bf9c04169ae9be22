"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRSML (``jit_TRSML_x<n>``, ``jit_TRSML``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRSML")
