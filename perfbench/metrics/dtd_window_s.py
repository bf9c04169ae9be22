"""DTD front end: host seconds per factorization the inserting thread
was held by the window (over ``dtd_window_size`` tasks outstanding,
until ``dtd_threshold_size``), waiting for completions; self time, so
less the tasks it ran and the engine passes it made meanwhile.  None
when it never was held (or the program has no such phase)."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("dtd_window",)) or None
