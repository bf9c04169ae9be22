"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRTRI (``jit_TRTRI_x<n>``, ``jit_TRTRI``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRTRI")
