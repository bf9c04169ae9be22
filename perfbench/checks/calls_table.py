#!/usr/bin/env python3
"""On the chip, by hand: one line per call of a cell's window, from the
call records the program always leaves.

    python3 perfbench/checks/calls_table.py --workload <cell> [--seed n] [--seconds 30] [--trace 0|1] [--out chiprun_out]

Runs the benchmark's own run of the cell (``perfbench/run.py``'s
``run_cell``: its set-up, its window, its check; its lines and its
result line are printed as they are), then reads the window's records
from ``parsec_tpu.obs.phases.completed()`` beside the harness's walls
and prints, per call: the wall, whether the profiler was on, the wall
seconds in each of the managers' six always-on brackets, summed over
the managers, the seconds no bracket holds, and a composed call's
parts.  Then the median untraced call and the slowest, what the slowest
one's excess is made of and who had it: ``chip`` (the managers waited
for the device), ``manager`` (they worked longer), or ``nobody`` (no
manager was in a bracket: workers parked or not scheduled, the
caller's thread, the operating system).  Then the untraced walls cut in
two at their widest gap, the mean call of each side by side: cell 1's
walls are two-valued.  With ``--trace 1`` (the default) the window's
second and third calls are traced as in the benchmark, so the table
shows what the spans cost beside what they measure.

Writes ``calls_table.<cell>.json`` under ``--out``.  A time here is a
chip time: ``run_cell`` refuses to run without the cell's chips.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import calls  # noqa: E402

COLUMNS = calls.BRACKETS + ("nobody",)


def row_of(rec, wall):
    """One call as numbers: wall seconds in each bracket (summed over
    the managers), and ``nobody``: the wall less the six brackets'
    mean over the managers."""
    n = max(1, len(rec["by_device"]))
    row = {"wall_s": wall, "traced": bool(rec["traced"]),
           "root_s": (rec["t1_ns"] - rec["t0_ns"]) / 1e9, "managers": n}
    for b in calls.BRACKETS:
        row[b] = calls.seconds_in(rec, (b,))
    row["nobody"] = wall - calls.seconds_in(rec, calls.BRACKETS) / n
    if "parts" in rec:
        row["parts"] = {p["name"]: (p["completed_ns"] - p["enqueued_ns"]) / 1e9
                        for p in rec["parts"]}
        row["compound_gap_s"] = rec["compound_gap_ns"] / 1e9
    return row


def summary(rows, how):
    """One row from many: ``how`` (a mean, a median) of each column."""
    return {k: how(r[k] for r in rows) for k in ("wall_s",) + COLUMNS}


def line(label, row):
    cells = "".join(f"{row[c]:>10.4f}" for c in COLUMNS)
    tail = ""
    if "parts" in row:
        tail = "  " + " ".join(f"{k}={v:.3f}"
                               for k, v in row["parts"].items()) \
            + f" gap={row['compound_gap_s']:.3f}"
    return f"{label:<16}{row['wall_s']:>9.4f}{cells}{tail}"


def classify(slow, median):
    """Who had the slowest call's excess over the median call."""
    n = slow["managers"]
    parts = {
        "chip": (slow["chip_wait"] - median["chip_wait"]) / n,
        "manager": sum(slow[b] - median[b] for b in calls.WORKING) / n,
        "nobody": slow["nobody"] - median["nobody"]}
    return max(parts, key=parts.get), parts


def two_values(rows):
    """The rows cut at the widest gap between consecutive walls: (fast
    side, slow side, the gap over the fast side's mean wall), or None
    with fewer than four."""
    if len(rows) < 4:
        return None
    ordered = sorted(rows, key=lambda r: r["wall_s"])
    at = max(range(1, len(ordered)),
             key=lambda i: ordered[i]["wall_s"] - ordered[i - 1]["wall_s"])
    fast, slow = ordered[:at], ordered[at:]
    gap = ordered[at]["wall_s"] - ordered[at - 1]["wall_s"]
    return fast, slow, gap / statistics.fmean(r["wall_s"] for r in fast)


def table(rows):
    """The printed table and what it found, from the window's rows."""
    out = [f"{'call':<16}{'wall s':>9}"
           + "".join(f"{c:>10}" for c in COLUMNS)]
    for i, row in enumerate(rows):
        out.append(line(f"{i + 1}{' traced' if row['traced'] else ''}", row))
    found = {}
    plain = [r for r in rows if not r["traced"]]
    spanned = [r for r in rows if r["traced"]]
    if not plain:
        return out + ["no untraced call in the window"], found
    median = summary(plain, statistics.median)
    out.append(line("median untraced", median))
    if spanned:
        out.append(line("mean traced", summary(spanned, statistics.fmean)))
    slow = max(plain, key=lambda r: r["wall_s"])
    out.append(line("slowest untraced", slow))
    who, parts = classify(slow, median)
    excess = slow["wall_s"] - median["wall_s"]
    found["slowest"] = {"wall_s": slow["wall_s"], "excess_s": excess,
                        "who": who, "parts_s": parts}
    out.append(f"slowest untraced wall {slow['wall_s']:.4f} s, "
               f"{excess:+.4f} s on the median call "
               f"({100.0 * excess / median['wall_s']:+.1f}%): "
               + ", ".join(f"{k} {v:+.4f}" for k, v in parts.items())
               + f" -> {who}")
    cut = two_values(plain)
    if cut:
        fast, slower, rel = cut
        found["two_values"] = {
            "fast": summary(fast, statistics.fmean), "n_fast": len(fast),
            "slow": summary(slower, statistics.fmean),
            "n_slow": len(slower), "gap_share": rel}
        out.append(f"untraced walls cut at their widest gap "
                   f"({100.0 * rel:.1f}% of the fast side's mean):")
        out.append(line(f"fast x{len(fast)}", found["two_values"]["fast"]))
        out.append(line(f"slow x{len(slower)}", found["two_values"]["slow"]))
    return out, found


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", default="", metavar="N,NB")
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    from perfbench import run as harness, spec
    seen = {}
    run_window = harness.run_window

    def keeping(fz, seconds, trace_dir):
        seen["window"] = run_window(fz, seconds, trace_dir)
        return seen["window"]

    harness.run_window = keeping    # the walls are the harness's own
    prefix = "REHEARSAL (CPU times, not measurements) " if args.rehearse \
        else ""

    def say(msg):
        print(f"{prefix}{msg}", flush=True)

    try:
        result = harness.run_cell(args, say)
    except (harness.Refused, spec.SpecError) as exc:
        print(f"calls_table: REFUSED: {exc}", file=sys.stderr)
        return 2
    finally:
        harness.run_window = run_window
    say(json.dumps(result))
    pairs = calls.window_calls({"walls": seen["window"].walls})
    if pairs is None:
        print("calls_table: the program left no record for the window's "
              "calls (or not one per wall)", file=sys.stderr)
        return 1
    rows = [row_of(rec, wall) for rec, wall in pairs]
    lines, found = table(rows)
    say(f"{args.workload}: {len(rows)} calls, seconds; brackets summed "
        f"over {rows[0]['managers']} manager(s), nobody = wall less "
        f"their mean")
    for text in lines:
        say(text)
    if args.rehearse:
        return 0
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"calls_table.{args.workload}.json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "trace": args.trace, "result": result, "calls": rows,
                   "found": found,
                   "by_device": [rec["by_device"] for rec, _w in pairs]},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
