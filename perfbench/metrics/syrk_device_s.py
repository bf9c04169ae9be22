"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class SYRK (``jit_SYRK_x<n>``, ``jit_SYRK``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "SYRK")
