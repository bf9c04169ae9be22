"""What the readers of one ``dev.stats`` counter per call share."""


def per_call(obs, name):
    """What the counter ``name`` moved by per call of the window, summed
    over the accelerator devices; None where the program has no such
    counter or the window counted no call."""
    counters = obs.get("counters") or {}
    if name not in counters or not obs.get("n_counted"):
        return None
    return counters[name] / obs["n_counted"]
