"""Matmul operand precisions of the chip, emulated on float32 numpy.

``highest`` keeps float32 operands (six bf16 passes on the MXU);
``high`` keeps 16 significant bits (bf16_3x: a high and a low bf16 half
of each operand, the low-by-low product dropped); ``default`` keeps 8
(one bf16 pass).  Accumulation is float32 in all three, as on the chip.
"""
import numpy as np

#: significant bits an operand keeps, per jax_default_matmul_precision
OPERAND_BITS = {"highest": 24, "high": 16, "default": 8}


def round_operand(x, precision):
    bits = OPERAND_BITS[precision]
    x = np.ascontiguousarray(x, dtype=np.float32)
    if bits >= 24:
        return x
    drop = 24 - bits
    u = x.view(np.uint32)
    # round to nearest even on the dropped mantissa bits
    bias = ((u >> drop) & 1) + ((1 << (drop - 1)) - 1)
    return ((u + bias.astype(np.uint32)) >> drop << drop).view(np.float32)


def matmul(a, b, precision):
    return round_operand(a, precision) @ round_operand(b, precision)
