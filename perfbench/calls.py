"""What the readers of the always-on call records share.

The program leaves one record per blocking ``ops`` call in
``parsec_tpu.obs.phases.completed()`` whether or not a profiler session
recorded: its two stamps, ``traced``, and ``manager``: what the device
managers' always-on brackets moved by during the call, per bracket
``wall_ns`` and ``count``, summed over the accelerator devices
(``by_device`` has each).  The brackets: ``set_stage`` (the look at a drained ready set's input flows
and its one ``device_put``), ``group`` (the rest of the drain's dispatch
pass: per-task stage-in bookkeeping, grouping, filing the records),
``dispatch`` (the device calls), ``epilog`` (installing a call's
outputs), ``complete`` (dependency release and the hand-over to the
scheduler) are the manager WORKING; ``chip_wait`` is the manager blocked
on the device.  On one device the six are disjoint.

The readers take the last ``len(obs["walls"])`` records, one per
factorization of the window in order, and want each root span inside
its wall as ``spans.traced_records`` does.  A ``--trace 1`` window has
``obs["n_traced"]`` calls with ``traced`` true (the profiler was on)
and the rest false: "untraced" numbers are means over the latter, per
factorization.  A program without such records, a record count that
does not match the walls, a window with no untraced call: all read as
nothing (None), never as zero.
"""
import statistics

from perfbench import spans

WORKING = ("set_stage", "group", "dispatch", "epilog", "complete")
BRACKETS = WORKING + ("chip_wait",)


def window_calls(obs):
    """[(record, wall seconds)] of the window's factorizations in
    order, or None."""
    try:
        from parsec_tpu.obs import phases
    except ImportError:
        return None
    walls = obs.get("walls")
    if not walls:
        return None
    records = phases.completed()[-len(walls):]
    if len(records) != len(walls):
        return None
    for rec, wall in zip(records, walls):
        if not rec.get("by_device"):   # no record of the brackets
            return None
        root = (rec["t1_ns"] - rec["t0_ns"]) / 1e9
        if not spans.ROOT_SPAN_SHARE_OF_WALL * wall <= root <= wall:
            return None
    return list(zip(records, walls))


def split(obs):
    """(untraced calls, traced calls) of the window, or None where
    there is no record or no untraced call."""
    calls = window_calls(obs)
    if calls is None:
        return None
    untraced = [c for c in calls if not c[0]["traced"]]
    if not untraced:
        return None
    return untraced, [c for c in calls if c[0]["traced"]]


def seconds_in(rec, names):
    """Wall seconds of one record in the brackets ``names``, summed over
    the managers."""
    return sum(rec["manager"][b]["wall_ns"] for b in names) / 1e9


def untraced_seconds(obs, name):
    """Wall seconds per untraced factorization inside the bracket
    ``name``, summed over the managers."""
    got = split(obs)
    if got is None:
        return None
    return statistics.fmean(seconds_in(rec, (name,)) for rec, _w in got[0])


def span_inflation_pct(obs):
    """What the five working brackets cost more in a traced
    factorization than in an untraced one of the same window, in
    percent of the untraced."""
    got = split(obs)
    if got is None or not got[1]:
        return None
    plain, traced = (statistics.fmean(seconds_in(rec, WORKING)
                                      for rec, _w in calls) for calls in got)
    if not plain:
        return None
    return 100.0 * (traced / plain - 1.0)


def slowest_wall(obs):
    """The window's slowest untraced factorization against its median
    untraced one: ``excess_s`` of the wall, the part of it in
    ``chip_wait_s``, and ``unaccounted_s`` found in none of the six
    brackets (no manager was working or waiting for the chip).  Bracket
    seconds are the mean over the managers here, so that on several
    chips the parts still add to the wall's excess; each median is
    taken on its own.  None with fewer than two untraced calls."""
    got = split(obs)
    if got is None or len(got[0]) < 2:
        return None
    calls = got[0]
    slow = max(calls, key=lambda c: c[1])

    def over_median(names):
        def mean_s(rec):
            return seconds_in(rec, names) / max(1, len(rec["by_device"]))
        return mean_s(slow[0]) - statistics.median(mean_s(rec)
                                                   for rec, _w in calls)

    excess = slow[1] - statistics.median(w for _rec, w in calls)
    return {"excess_s": excess, "chip_wait_s": over_median(("chip_wait",)),
            "unaccounted_s": excess - over_median(BRACKETS)}
