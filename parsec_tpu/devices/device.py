"""Device module base + registry + owner-computes placement.

Reference behavior: ``parsec_device_module_t`` {attach, taskpool_register,
memory_register, data_advise, ...} with per-device capability weights and
``parsec_get_best_device`` (ref: parsec/mca/device/device.c:79-168,
device.h:77-125): a task runs on the accelerator that OWNS the data it
writes; a written tile no accelerator owns yet goes where
``data_advise(.., "preferred_device")`` said, else to the least loaded
device (``device_load`` + the task's estimate).  So advice or load
decides only a tile's first touch, and every later update of the tile
finds it where it is: what still crosses between chips is what a task
only READS.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from ..data.data import FlowAccess
from ..obs.phases import PLACED_BY  # noqa: F401  (the rules' counters)


class Device:
    """ref: parsec_device_module_t"""

    def __init__(self, device_type: str, device_index: int, name: str = "") -> None:
        self.device_type = device_type
        self.device_index = device_index
        self.name = name or f"{device_type}:{device_index}"
        self.device_load = 0.0          # outstanding estimated work (ns-ish)
        self.time_estimate_default = 1.0  # per-task default cost weight
        self.executed_tasks = 0
        self._load_lock = threading.Lock()
        # telemetry sink (obs.spans.DeviceObs); wired by ContextObs —
        # None keeps transfer sites on the one-attribute-check fast path
        self._obs = None
        # the open root span's phase clock (obs.phases.root_span sets
        # and clears it); None keeps the span sites on the same
        # one-attribute-check fast path
        self._phases = None

    # registration hooks (no-ops by default)
    def taskpool_register(self, tp) -> None:
        pass

    def taskpool_unregister(self, tp) -> None:
        pass

    def memory_register(self, buf) -> None:
        pass

    def memory_unregister(self, buf) -> None:
        pass

    def data_advise(self, data, advice: str) -> None:
        """advice in {"prefetch", "preferred_device", "warmup"}
        (ref: parsec_mca_device_data_advise)."""

    def load_add(self, est: float) -> None:
        with self._load_lock:
            self.device_load += est

    def load_sub(self, est: float) -> None:
        with self._load_lock:
            self.device_load = max(0.0, self.device_load - est)

    def progress(self, es) -> int:
        """Advance asynchronous work; returns the number of pipeline
        steps handled (completions AND submissions — a batched device
        flushing its accumulated ready queue made progress even when
        nothing finished yet)."""
        return 0

    def drain(self, context=None) -> None:
        """Flush the device pipeline at a run boundary: retire trailing
        in-flight records (recording async errors on ``context``) and
        discard ready-queue entries stranded by a DAG abort.  Called by
        ``Context.wait()`` exit and the FT rollback path
        (``Context._drain_devices``); no-op for synchronous devices."""

    def fini(self) -> None:
        pass

    def __repr__(self) -> str:  # pragma: no cover
        return f"<Device {self.name} load={self.device_load:.1f}>"


def get_best_device(task, devices: List[Device],
                    eligible_types: Optional[set] = None) -> Device:
    """ref: parsec_get_best_device (device.c:79-168).

    1. The first flow the task writes whose tile one of the eligible
       devices owns decides: the task runs there.
    2. Otherwise (first touch: the host owns every written tile; or
       the task writes no tile of a collection): the first written
       tile's ``preferred_device`` if it names an eligible device,
       else the least ``device_load`` + estimate.

    The chosen device's ``stats`` count which rule placed the task
    (``PLACED_BY``: ``placed_by_owner`` / ``placed_by_advice`` /
    ``placed_by_load``).  With one eligible device there is nothing to
    decide and nothing is counted.
    """
    if eligible_types is not None:
        devices = [d for d in devices if d.device_type in eligible_types]
    assert devices, "no eligible device"
    if len(devices) == 1:
        return devices[0]
    tc = task.task_class
    by_index = {d.device_index: d for d in devices}
    advised = None
    for flow in tc.flows:
        if flow.ctl or not task.access_of(flow) & FlowAccess.WRITE:
            continue
        din = task.data[flow.flow_index].data_in
        data = din.data if din is not None else None
        if data is None:
            continue
        owner = by_index.get(data.owner_device)
        if owner is not None:
            return _placed(owner, "placed_by_owner")
        if advised is None:
            advised = by_index.get(data.preferred_device)
    if advised is not None:
        return _placed(advised, "placed_by_advice")
    estimate = tc.time_estimate

    def score(dev: Device) -> float:
        return dev.device_load + (dev.time_estimate_default
                                  if estimate is None
                                  else estimate(task, dev))
    # a tie goes to the first device of the list
    return _placed(min(devices, key=score), "placed_by_load")


def _placed(dev: Device, rule: str) -> Device:
    # workers place tasks concurrently; the count must add up to the
    # tasks placed
    with dev._load_lock:
        dev.stats[rule] += 1
    return dev
