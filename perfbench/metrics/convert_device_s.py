"""Tile kernels: device seconds per factorization inside the reshape
engine's conversion programs (``jit_CONVERT``, one call a converted
tile: ``parsec_tpu/data/reshape.py``), mean over the chips.  None where
the trace names no such program (a program without the engine's device
path, a cell that converts nothing)."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "CONVERT")
