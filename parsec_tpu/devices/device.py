"""Device module base + registry + load-balanced placement.

Reference behavior: ``parsec_device_module_t`` {attach, taskpool_register,
memory_register, data_advise, ...} with per-device capability weights and
``parsec_get_best_device`` = min(load + ratio*weight) with a sticky-device
skew toward where the data already lives
(ref: parsec/mca/device/device.c:79-168, device.h:77-125).
"""
from __future__ import annotations

import threading
from typing import Any, List, Optional

from ..utils.params import params


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

    Sticky skew: a device already holding a valid copy of one of the task's
    written flows gets a ``device_load_balance_skew`` percent discount.
    """
    skew = params.get("device_load_balance_skew") / 100.0
    best, best_score = None, None
    data_homes = set()
    for ref in task.data:
        din = ref.data_in
        if din is not None and din.data is not None:
            od = din.data.owner_device
            if od >= 0:
                data_homes.add(od)
    for dev in devices:
        if eligible_types is not None and dev.device_type not in eligible_types:
            continue
        est = dev.time_estimate_default
        tc = task.task_class
        if tc.time_estimate is not None:
            est = tc.time_estimate(task, dev)
        score = dev.device_load + est
        if dev.device_index in data_homes:
            score *= (1.0 - skew)
        if best_score is None or score < best_score:
            best, best_score = dev, score
    assert best is not None, "no eligible device"
    return best
