"""Scheduler and dependency release: seconds per factorization the
interpreter's garbage collector ran inside the timed calls (gc.callbacks;
it holds the interpreter lock, so every runtime thread waits)."""


def read(obs):
    if not obs["n_counted"] or "gc_s" not in obs["counters"]:
        return None
    return obs["counters"]["gc_s"] / obs["n_counted"]
