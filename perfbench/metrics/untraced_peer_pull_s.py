"""Data movement: wall seconds per untraced call of the entry point
that the device managers spent inside chip-to-chip ``device_put`` calls
for a stage-in (``peer_pull_ns`` of every call record's
``by_device[*]["peer"]``, summed over the managers, mean over the
window's untraced calls; ``perfbench/calls.py``).  Always on, a
``time.monotonic_ns`` reading at both ends of each call; inside the
bracket ``group`` (the per-task stage-in is), so ``untraced_group_s``
holds it.  It is the THREAD's time: what the pull costs the chip and the
panel chain shows in the device trace.  None where the records have no
``peer`` block (the parent of the PR that added it)."""
import statistics

from perfbench import calls


def read(obs):
    got = calls.split(obs)
    if got is None:
        return None
    try:
        return statistics.fmean(
            sum(e["peer"]["peer_pull_ns"] for e in rec["by_device"]) / 1e9
            for rec, _wall in got[0])
    except KeyError:
        return None
