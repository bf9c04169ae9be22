#!/usr/bin/env python3
"""On the chip, by hand: what the runtime's threads were doing while the
chip sat idle.

    python3 perfbench/checks/idle_by_phase.py --workload <cell> [--seed n] [--out chiprun_out]

Runs the cell's set-up (a cold and a warm factorization), then traces
the benchmark's ``N_TRACED`` factorizations under the benchmark's own
spans, and reads the trace with the ``parsec:`` spans kept (the
harness's ``xplane.read`` keeps only ``perfbench:`` ones).  Every host
thread's time is cut into the innermost ``parsec:`` / ``perfbench:``
span covering it; every device idle gap inside the traced window is
then split over those pieces, three ways:

- ``caller``: by the span of the calling thread (the line that holds
  ``perfbench:entry_call``); sums to the idle time;
- ``any``: seconds of idle time during which at least one thread was
  inside the phase (phases overlap across threads; ``nobody`` is idle
  time with every thread parked, polling or outside every span);
- ``mean``: thread-seconds over the number of threads; sums to the
  idle time.

Prints the program's own phase table of each traced factorization
(``parsec_tpu.obs.phases.format_report``), then the idle table, and
writes both as ``idle_by_phase.<cell>.json`` under ``--out``.  A time here is a chip time; the tool refuses to run
without the cell's chips.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PREFIXES = ("parsec:", "perfbench:")
#: a thread whose innermost span is one of these is not working on the
#: DAG (``parsec:op`` is: the calling thread outside every phase)
WAITING = {"parsec:parked", "parsec:idle_poll", "parsec:select",
           "outside_spans", "perfbench:traced", "perfbench:entry_call"}


def host_lines(pd):
    """[[(name, start, end)]] per host thread that wrote one of our
    spans, sorted by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, float(ev.start_ns),
                      float(ev.start_ns) + float(ev.duration_ns))
                     for ev in line.events if ev.name.startswith(PREFIXES)]
            if spans:
                out.append(sorted(spans, key=lambda t: (t[1], -t[2])))
    return out


def innermost(spans, lo, hi):
    """Cut [lo, hi] into (start, end, name) pieces by the innermost
    span of one thread covering each instant (spans of one thread
    nest); uncovered time is ``outside_spans``."""
    pieces, stack = [], []     # stack of (name, end)
    t = lo

    def emit(until):
        nonlocal t
        until = min(until, hi)
        if until > t:
            pieces.append((t, until, stack[-1][0] if stack
                           else "outside_spans"))
            t = until

    for name, s, e in spans:
        if e <= lo or s >= hi:
            continue
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, e))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return pieces


def overlap(pieces, gaps):
    """{name: seconds} of ``pieces`` inside the sorted disjoint
    ``gaps``."""
    out, gi = {}, 0
    for s, e, name in pieces:
        while gi < len(gaps) and gaps[gi][1] <= s:
            gi += 1
        k = gi
        while k < len(gaps) and gaps[k][0] < e:
            part = min(e, gaps[k][1]) - max(s, gaps[k][0])
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
            k += 1
    return out


def attribute(pd, xplane):
    """The three splits of the device idle time of a trace."""
    tr = xplane.read(pd)
    traced = [(s, e) for name, s, e in tr["spans"] if name == "traced"]
    chips = {i: c for i, c in tr["chips"].items() if c["ops"] or c["modules"]}
    if not traced or not chips:
        return None
    lo, hi = traced[0]
    lines = host_lines(pd)
    caller = next((i for i, spans in enumerate(lines)
                   if any(n == "perfbench:entry_call" for n, _, _ in spans)),
                  None)
    cut = [innermost(spans, lo, hi) for spans in lines]
    by_caller, by_any, by_mean, idle_s = {}, {}, {}, 0.0
    for c in chips.values():
        src = c["ops"] or c["modules"]
        busy = xplane.merge(xplane.clip([(s, s + d) for _, s, d in src],
                                        lo, hi))
        edges = [lo] + [t for se in busy for t in se] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_s += sum(b - a for a, b in gaps) / 1e9 / len(chips)
        per_thread = [overlap(p, gaps) for p in cut]
        for i, table in enumerate(per_thread):
            for name, sec in table.items():
                by_mean[name] = by_mean.get(name, 0.0) \
                    + sec / len(cut) / len(chips)
                if i == caller:
                    by_caller[name] = by_caller.get(name, 0.0) \
                        + sec / len(chips)
        names = {n for p in cut for _, _, n in p}
        covered = []
        for name in names - WAITING:
            union = xplane.merge([(s, e) for p in cut
                                  for s, e, n in p if n == name])
            sec = overlap([(s, e, name) for s, e in union], gaps)
            by_any[name] = by_any.get(name, 0.0) \
                + sec.get(name, 0.0) / len(chips)
            covered.extend(union)
        worked = overlap([(s, e, "x") for s, e in xplane.merge(covered)],
                         gaps).get("x", 0.0)
        by_any["nobody"] = by_any.get("nobody", 0.0) \
            + (sum(b - a for a, b in gaps) / 1e9 - worked) / len(chips)
    nested = sum(1 for spans in lines for n, s, e in spans if n == "parsec:op"
                 and any(m == "perfbench:entry_call" and s2 <= s and e <= e2
                         for m, s2, e2 in spans))
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle_s,
            "host_threads": len(lines), "chips": len(chips),
            "root_spans_inside_entry_call": nested,
            "caller": by_caller, "any": by_any, "mean": by_mean}


def table(result):
    names = sorted(set(result["caller"]) | set(result["any"])
                   | set(result["mean"]),
                   key=lambda n: -result["mean"].get(n, 0.0))
    rows = [f"{'span':<24}{'caller s':>10}{'any s':>10}{'mean s':>10}"]
    for n in names:
        rows.append(f"{n:<24}"
                    + "".join(f"{result[k][n]:>10.4f}" if n in result[k]
                              else f"{'':>10}"
                              for k in ("caller", "any", "mean")))
    return "\n".join(rows)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--xplane", default="",
                    help="read this recorded .xplane.pb instead of running")
    args = ap.parse_args(argv)
    from perfbench import run as harness, spec, xplane
    if args.xplane:
        result = attribute(xplane.load(args.xplane), xplane)
    else:
        import jax
        cell = spec.Cell(spec.load_benchmark(), args.workload)
        jax.config.update("jax_default_matmul_precision",
                          cell.config["matmul_precision"])
        try:
            harness.gate_device(jax, cell, rehearse=False)
        except (harness.Refused, spec.SpecError) as exc:
            print(f"idle_by_phase: REFUSED: {exc}", file=sys.stderr)
            return 2
        import parsec_tpu
        M = harness.seeded_input(cell.reference(), cell, args.seed)
        ctx = parsec_tpu.init()
        tmp = tempfile.mkdtemp(prefix="perfbench_trace_")
        try:
            fz = harness.Factorizer(jax, ctx, cell, M,
                                    harness.HostClocks(jax))
            for _ in range(3):      # cold, warm, the window's untraced first
                fz.factor(fz.tile())
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("perfbench:traced"):
                    for _ in range(harness.N_TRACED):
                        with jax.profiler.TraceAnnotation(
                                "perfbench:tile_input"):
                            A = fz.tile()
                        with jax.profiler.TraceAnnotation(
                                "perfbench:entry_call"):
                            fz.factor(A)
            finally:
                jax.profiler.stop_trace()
            result = attribute(xplane.load(xplane.find_xplane(tmp)), xplane)
            if result is not None:
                from parsec_tpu.obs import phases
                result["records"] = [r for r in phases.completed()
                                     if r["traced"]]
                for rec in result["records"]:
                    print(phases.format_report(rec))
        finally:
            ctx.fini()
            shutil.rmtree(tmp, ignore_errors=True)
    if result is None:
        print("idle_by_phase: the trace holds no traced window or no "
              "device operation", file=sys.stderr)
        return 1
    result["workload"] = args.workload
    print(f"{args.workload}: traced window {result['window_s']:.4f} s, "
          f"device idle {result['idle_s']:.4f} s (mean over "
          f"{result['chips']} chip(s)), {result['host_threads']} host "
          f"threads with spans, {result['root_spans_inside_entry_call']} "
          f"parsec:op span(s) nested inside perfbench:entry_call")
    print(table(result))
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"idle_by_phase.{args.workload}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
