"""Device: seconds per traced call the BUSIEST chip ran device
operations (the union of its ``XLA Ops`` intervals inside the traced
window; ``xplane.reduce``: ``busy_by_chip_s``).  ``device_idle_pct``
and the ``<class>_device_s`` are means over the chips; this is the
chip the others wait for.  None without a trace."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr.get("busy_by_chip_s") or not obs.get("n_traced"):
        return None
    return max(tr["busy_by_chip_s"].values()) / obs["n_traced"]
