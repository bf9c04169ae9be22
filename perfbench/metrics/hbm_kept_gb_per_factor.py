"""Device module: bytes the chips still hold after a factorization that
they did not hold after the one before it (the median over the window's
factorizations, summed over the chips).  The device module's LRU keeps
every copy it ever staged, of dead collections and of the WRITE-only
scratch flows too, until its budget is full; from then on this reads 0
and ``evictions`` move instead."""
import statistics


def read(obs):
    in_use = obs.get("memory_in_use_bytes") or []
    if len(in_use) < 2 or not any(in_use):
        return None
    return statistics.median(b - a for a, b in zip(in_use, in_use[1:])) / 1e9
