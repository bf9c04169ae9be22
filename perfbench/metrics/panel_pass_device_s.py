"""Tile kernels: device seconds per factorization of the STRIP PASSES of
LU's pivoted panel: what class PANEL's programs take
(``panel_device_s``) less the Mosaic kernel that factors the strips in
VMEM (``panel_strip_device_s``).  What is left is what every strip makes
the rest of the panel pay: the strip cut out and put back, the rows its
interchanges moved, the small solve and the product right of it (the
Mosaic kernel ``lu_pass_vmem`` where the program has one, whole-panel
gathers and rewrites in XLA where it does not), and once a panel the
interchanges of the columns already factored.  Mean over the chips.
Nothing where the trace names no PANEL program or no strip kernel."""
from perfbench import spec


def read(obs):
    panel = spec.metric_reader("panel_device_s").read(obs)
    strip = spec.metric_reader("panel_strip_device_s").read(obs)
    if panel is None or strip is None:
        return None
    return panel - strip
