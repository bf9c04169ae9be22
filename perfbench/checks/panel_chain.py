#!/usr/bin/env python3
"""On the chip, by hand: a ``--trace 1`` run of an LU cell with the
trace's EVENTS kept, so that ``panel_chain_gap_s`` can be read.

    python3 perfbench/checks/panel_chain.py --workload <cell> --seed <n> [--seconds 30]

The run IS the benchmark's (``perfbench/run.py``'s ``main``, same
process, same result line on standard output): ``xplane.reduce`` is
wrapped for this process so that its argument is kept
(``xplane.read``'s per-chip ``modules`` events and the benchmark's
spans, which the harness's reduction drops).  After the
result line it prints one more JSON line: ``panel_chain_gap_s``, the
gaps of each traced call, and per chip the PANEL programs it ran and
their seconds.  Refuses what the harness refuses (no TPU, the wrong
number of chips).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", default="", metavar="N,NB")
    args = ap.parse_args(argv)
    from perfbench import run as harness, spec, xplane
    kept = {}
    reduce = xplane.reduce

    def keeping_events(trace, window=None):
        kept["events"] = trace
        return reduce(trace, window)

    xplane.reduce = keeping_events
    # the readers of a class's roofline take the cell from the command line
    sys.argv = [os.path.join(ROOT, "perfbench", "run.py"),
                "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"] \
        + (["--rehearse", args.rehearse] if args.rehearse else [])
    rc = harness.main(sys.argv[1:])
    events = kept.get("events")
    if rc or not events:
        return rc or 1
    reader = spec.metric_reader("panel_chain_gap_s")
    gaps = reader.chain_gaps(events)
    by_chip = {i: [d / 1e9 for name, _s, d in c["modules"]
                   if reader.PANEL.match(name)]
               for i, c in sorted(events["chips"].items())}
    print(json.dumps({
        "panel_chain_gap_s": reader.read({"trace": {"events": events}}),
        "gaps_by_call_s": gaps,
        "panels_by_chip": {i: len(v) for i, v in by_chip.items()},
        "panel_s_by_chip": {i: sum(v) for i, v in by_chip.items()}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
