"""Tile kernels: the least time the chip could take for the DAG's
GEMM_LO tasks (count x max(flops / peak, bytes / bandwidth) of
``kernels/<operation>.GEMM_LO.json``: two bf16 operands and an f32 tile
read, an f32 tile written) over the device seconds of the class's
programs per factorization (``gemm_lo_device_s``), against the
published bf16 peak: one pass, so up to 100; ``class_roofline.py``."""
from perfbench import class_roofline


def read(obs):
    return class_roofline.read(obs, "GEMM_LO")
