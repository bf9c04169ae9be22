"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and the
operations that took most time.  Needs nothing but JAX's own reader.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, read by hand in
PR 23): one plane per chip, ``/device:TPU:<i>``, whose line ``XLA Ops``
holds one event per executed HLO operation and whose line ``XLA
Modules`` one per executed program; host threads are lines of the plane
``/host:CPU``, where the benchmark's own ``TraceAnnotation`` spans
(names starting ``perfbench:``) land.  All planes share one clock to
within about a millisecond: in the recorded trace a program's device
events start 0.9 ms before the host event that launched it, so an idle
gap of a few milliseconds or more is attributed to the right span and a
shorter one may not be.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "perfbench:"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def read(pd):
    """{"chips": {i: {"ops": [(name, start, dur)], "modules": [...]}},
    "spans": [(name, start, end)]}, times in ns on the trace's clock."""
    chips, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    rec["modules"] = _events(line)
            chips[int(m.group(1))] = rec
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((ev.name[len(SPAN_PREFIX):], s,
                                      s + float(ev.duration_ns)))
    spans.sort(key=lambda t: t[1])
    return {"chips": chips, "spans": spans}


def _label(spans, t):
    """The innermost of the benchmark's spans that covers time t."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside_spans"


def reduce(trace, window=None):
    """Busy seconds, idle gaps and top operations of a ``read`` trace.

    ``window`` is (start, end) in ns; by default the span named
    ``traced`` if the benchmark wrote one, else first device event to
    last.  Busy time of a chip is the union of its ``XLA Ops``
    intervals inside the window (its ``XLA Modules`` where a trace has
    no ops line); ``busy_s`` is the mean over the chips that ran
    anything."""
    chips = {i: c for i, c in trace["chips"].items()
             if c["ops"] or c["modules"]}
    if not chips:
        return None
    spans = trace["spans"]
    if window is None:
        traced = [(s, e) for name, s, e in spans if name == "traced"]
        if traced:
            window = traced[0]
        else:
            ev = [x for c in chips.values()
                  for x in (c["ops"] or c["modules"])]
            window = (min(s for _, s, _ in ev),
                      max(s + d for _, s, d in ev))
    lo, hi = window
    inner = [sp for sp in spans if sp[0] != "traced"]
    busy, op_time, mod_time, gaps = {}, {}, {}, []
    for i, c in chips.items():
        src = c["ops"] or c["modules"]
        union = merge(clip([(s, s + d) for _, s, d in src], lo, hi))
        busy[i] = sum(e - s for s, e in union) / 1e9
        for table, events in ((op_time, c["ops"]), (mod_time, c["modules"])):
            for name, s, d in events:
                part = min(s + d, hi) - max(s, lo)
                if part > 0:
                    table[name] = table.get(name, 0.0) + part / 1e9
        edges = [lo] + [t for se in union for t in se] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label(inner, (a + b) / 2), (b - a) / 1e9, i))
    n = len(chips)
    by_label = {}
    for name, sec, _ in gaps:
        by_label[name] = by_label.get(name, 0.0) + sec / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy.values()) / n,
        "busy_by_chip_s": busy,
        "ops_s": {k: v / n for k, v in op_time.items()},
        "modules_s": {k: v / n for k, v in mod_time.items()},
        "idle_by_label_s": by_label,
        "longest_gaps": sorted(((name, sec) for name, sec, _ in gaps),
                               key=lambda t: -t[1])[:10],
    }


def short_name(op):
    """'%fusion.3 = f32[...] fusion(...)' -> '%fusion.3', with the
    target of a custom call kept."""
    name = op.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', op)
    return f"{name}:{m.group(1)}" if m else name


def top(table, k=10):
    """The k largest of {name: seconds} as [[short name, seconds]];
    operations of different programs that share a short name add up."""
    short = {}
    for name, sec in table.items():
        key = short_name(name)
        short[key] = short.get(key, 0.0) + sec
    return [[name, sec] for name, sec in
            sorted(short.items(), key=lambda t: -t[1])[:k]]


def describe(pd, limit=6):
    """Plane, line and first-event names: what one reads by hand before
    writing code against a trace."""
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                out.append(f"    {ev.name[:100]!r} start_ns={ev.start_ns} "
                           f"dur_ns={ev.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(load(sys.argv[1])))
