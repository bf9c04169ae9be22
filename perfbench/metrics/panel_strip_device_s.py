"""Tile kernels: device seconds per factorization inside the Mosaic
kernel that factors one strip of LU's pivoted panel with the strip held
in VMEM (``parsec_tpu.ops.pallas_kernels.lu_strip_vmem``, 16 calls a
PANEL task at NB = 512).  Read from the trace's ``XLA Ops`` line, where
a Mosaic call goes by the kernel's ``name``: ``%lu_strip_vmem[.<n>] =
... custom_call_target="tpu_custom_call"``.  Mean over the chips.  What
is left of ``panel_device_s`` beside it is the strip passes (the
whole-panel gather, the small solve, the product).  Nothing where the
trace holds no such operation: a program whose panel runs the XLA loop,
an untraced run."""
import re

from perfbench import xplane

KERNEL = re.compile(r"^%?lu_strip_vmem(\.\d+)?:tpu_custom_call$")


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("n_traced"):
        return None
    secs = [s for op, s in tr["ops_s"].items()
            if KERNEL.match(xplane.short_name(op))]
    if not secs:
        return None
    return sum(secs) / obs["n_traced"]
