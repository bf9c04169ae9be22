"""What the readers of a composed call share: the per-part records the
program leaves in a traced call's phase record (``parsec_tpu.obs.phases``:
``parts``, one per taskpool of the compound, with the nanoseconds it was
enqueued, its first device call left and it completed; and
``compound_gap_ns``).  A program without them, an untraced run or a call
that composed nothing reads as nothing (None)."""
from perfbench import spans


def gap_seconds(obs):
    """Seconds per factorization between one part's completion and the
    next part's first device call, summed over the call's boundaries."""
    records = spans.traced_records(obs)
    if not records or any("compound_gap_ns" not in r for r in records):
        return None
    return sum(r["compound_gap_ns"] for r in records) / 1e9 / len(records)


def part_seconds(obs, name):
    """Seconds per factorization from the enqueue of the part (taskpool)
    called ``name`` to its completion."""
    records = spans.traced_records(obs)
    if not records:
        return None
    walls = []
    for rec in records:
        part = next((p for p in rec.get("parts", ())
                     if p["name"] == name), None)
        if part is None or not part["completed_ns"]:
            return None
        walls.append(part["completed_ns"] - part["enqueued_ns"])
    return sum(walls) / 1e9 / len(walls)
