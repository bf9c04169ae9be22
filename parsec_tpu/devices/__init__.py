"""Device MCA framework: registry + construction.

ref: parsec_mca_device_init/attach (parsec/parsec.c:832-837), component
selection via MCA param ``device_tpu_enabled`` (analog of
``device_cuda_enabled`` used throughout the reference test suite).
"""
from __future__ import annotations

from typing import List

from ..utils import logging as plog
from ..utils.params import params
from .cpu import CPUDevice
from .device import Device, get_best_device

params.reg_bool("device_tpu_enabled", True, "attach XLA devices as accelerators")
params.reg_int("device_tpu_max", -1, "max number of XLA devices to attach (-1 all)")
params.reg_string("device_tpu_platform", "",
                  "XLA platform to attach (tpu|cpu|...); empty = jax default")


def build_devices(context, enable_tpu: bool = True) -> List[Device]:
    devices: List[Device] = [CPUDevice(0)]
    if enable_tpu and params.get("device_tpu_enabled"):
        import jax
        plat = params.get("device_tpu_platform")
        try:
            # local: under jax.distributed the global list also holds
            # the other processes' devices, which nobody here can drive
            jdevs = jax.local_devices(backend=plat or None)
        except RuntimeError as exc:
            # no silent host-only run: a caller that wants one says so
            # (device_tpu_enabled=0 / enable_tpu=False)
            from ..utils.show_help import show_help
            raise RuntimeError(show_help(
                "help-runtime.txt", "tpu-device-unavailable",
                want_error=True, platform=plat or "jax default",
                error=exc)) from exc
        cap = params.get("device_tpu_max")
        if cap >= 0:
            jdevs = jdevs[:cap]
        mesh_dev = _maybe_mesh_device(context, jdevs)
        if mesh_dev is not None:
            devices.append(mesh_dev)
            plog.device_stream.verbose(
                3, "attached mesh device %s over %d chip(s)",
                mesh_dev.name, len(mesh_dev.chips))
            return devices
        from .tpu import JaxDevice
        for i, jd in enumerate(jdevs):
            devices.append(JaxDevice(1 + i, jd))
        if jdevs:
            plog.device_stream.verbose(3, "attached %d XLA device(s): %s",
                                       len(jdevs), [d.name for d in devices[1:]])
    return devices


def _maybe_mesh_device(context, jdevs):
    """Build the rank's chip-mesh device when ``device_mesh_shape``
    asks for one (ISSUE 6): this rank takes a contiguous slice of the
    local chips offset by rank*chips (in-process SPMD ranks carve
    disjoint sub-meshes of the virtual device pool; a multi-process
    deployment owns its local chips outright). A shape the attached
    chips cannot seat is an error."""
    shape = params.get("device_mesh_shape")
    if not shape or not jdevs:
        return None
    from .tpu import JaxMeshDevice, parse_mesh_shape
    gp, gq = parse_mesh_shape(shape)
    need = gp * gq
    if need <= 1:
        return None
    if len(jdevs) < need:
        raise RuntimeError(
            f"device_mesh_shape={shape} needs {need} chips, this "
            f"process has {len(jdevs)} ({[str(d) for d in jdevs]})")
    rank = int(getattr(context, "rank", 0) or 0)
    off = (rank * need) % len(jdevs)
    chips = (list(jdevs) * 2)[off:off + need]   # wraps, stays distinct
    return JaxMeshDevice(1, chips, (gp, gq))


from .template import TemplateDevice, template_chore_hook  # noqa: E402

__all__ = ["Device", "CPUDevice", "build_devices", "get_best_device",
           "TemplateDevice", "template_chore_hook", "JaxMeshDevice"]


def __getattr__(name):
    # lazy: importing the package must not import jax-heavy tpu.py
    if name == "JaxMeshDevice":
        from .tpu import JaxMeshDevice
        return JaxMeshDevice
    raise AttributeError(name)
