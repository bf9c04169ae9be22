"""What the per-layer readers of the runtime's own spans share.

The program keeps one record per blocking ``ops`` call made while a
profiler session recorded (``parsec_tpu.obs.phases.completed()``: per
phase the self time, summed over the runtime's threads).  A ``--trace
1`` run traces ``obs["n_traced"]`` factorizations of its window, so
the readers take the records flagged ``traced`` and want exactly that
many, each with a root span inside its factorization's wall (the wall
also waits for the chip: root span <= wall, within 20%).  Anything
else - a program without the phase clock, an untraced run - reads as
nothing (None), never as zero.

Dispatched programs carry their task class in the device trace: ``XLA
Modules`` events are named ``jit_<CLASS>_x<n>(<fingerprint>)`` for a
stacked batch of n tasks and ``jit_<CLASS>(...)`` for one task.
"""
import re

ROOT_SPAN_SHARE_OF_WALL = 0.8


def traced_records(obs):
    """The phase records of the traced factorizations, or None."""
    try:
        from parsec_tpu.obs import phases
    except ImportError:
        return None
    n = obs.get("n_traced")
    if not n:
        return None
    records = [r for r in phases.completed() if r.get("traced")]
    # the profiler starts before the window's second factorization
    walls = obs["walls"][1:1 + n]
    if len(records) != n or len(walls) != n:
        return None
    for rec, wall in zip(records, walls):
        root = (rec["t1_ns"] - rec["t0_ns"]) / 1e9
        if not ROOT_SPAN_SHARE_OF_WALL * wall <= root <= wall:
            return None
    return records


def phase_seconds(obs, names):
    """Self seconds of the phases ``names`` per factorization, summed
    over every thread of the runtime; None where there are no records."""
    records = traced_records(obs)
    if records is None:
        return None
    ns = sum(rec["phases"].get(name, {}).get("self_ns", 0)
             for rec in records for name in names)
    return ns / 1e9 / len(records)


def class_device_seconds(obs, cls):
    """Device seconds per factorization inside the programs dispatched
    for task class ``cls`` (``XLA Modules`` durations, mean over the
    chips); None where the trace names no program for the class."""
    tr = obs.get("trace")
    if not tr or not obs.get("n_traced"):
        return None
    named = re.compile(r"^jit_" + re.escape(cls) + r"(_x\d+)?(\(|$)")
    secs = [s for name, s in tr["modules_s"].items() if named.match(name)]
    if not secs:
        return None
    return sum(secs) / obs["n_traced"]
