"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class PANEL (``jit_PANEL_x<n>``, ``jit_PANEL``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "PANEL")
