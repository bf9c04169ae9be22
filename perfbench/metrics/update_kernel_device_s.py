"""Tile kernels: device seconds per factorization inside the Mosaic
kernel that walks one block column right of LU's panel
(``parsec_tpu.ops.pallas_kernels.lu_update_vmem``, one call an UPDATE
task: 496 a factorization at NT = 32): the rows the panel's pivots moved
stored, the product subtracted under the block row and nowhere else, the
blocks above the panel's first row passed through.
Read from the trace's ``XLA Ops`` line, where a Mosaic call goes by the
kernel's ``name``: ``%lu_update_vmem[.<n>] = ...
custom_call_target="tpu_custom_call"``.  Mean over the chips.  What is
left of ``update_device_s`` beside it is the gather of the 2 NB moved
rows and the solve of the block row.  Nothing where the trace holds no such operation: a program whose
update runs in XLA (the parent; any platform but the TPU), an untraced
run."""
import re

from perfbench import xplane

KERNEL = re.compile(r"^%?lu_update_vmem(\.\d+)?:tpu_custom_call$")


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("n_traced"):
        return None
    secs = [s for op, s in tr["ops_s"].items()
            if KERNEL.match(xplane.short_name(op))]
    if not secs:
        return None
    return sum(secs) / obs["n_traced"]
