"""Device: 1 - union of device-operation intervals over the traced
window, the mean over the cell's chips."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
