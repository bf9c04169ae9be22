"""Tile kernels: the least time the chip could take for the DAG's
GEMM_MID tasks (count x max(flops / peak, bytes / bandwidth) of
``kernels/<operation>.GEMM_MID.json``) over the device seconds of the
class's programs per factorization (``gemm_mid_device_s``), against the
published bf16 peak: three passes, so up to 33;
``class_roofline.py``."""
from perfbench import class_roofline


def read(obs):
    return class_roofline.read(obs, "GEMM_MID")
