"""Device: seconds per traced call between LU's panels on the chips'
own clock: the sum over k of the start of ``jit_PANEL`` number k + 1
less the end of number k, whichever chips ran them (``XLA Modules``
events of every chip's plane, in start order inside one
``perfbench:entry_call`` span: PANEL(k + 1) waits for UPDATE(k, k + 1),
which waits for PANEL(k), so a call's panels never overlap).  What the
critical path waits for between two panels: the pull of the panel to the
chip that owns the next column, UPDATE(k, k + 1), and the hosts' hops
between them; ``panel_device_s`` times the chips beside it is the chain
itself.

Reads the EVENTS of a trace (``xplane.read``'s ``{"chips": {i:
{"modules": [(name, start_ns, duration_ns)]}}, "spans": [(name,
start_ns, end_ns)]}``) under ``obs["trace"]["events"]``.  The harness's
``xplane.reduce`` keeps sums by name and no event, so in a benchmark
run this reads nothing and ``BENCHMARK.json`` does not list it (PERF.md
section 7); ``perfbench/checks/panel_chain.py`` (by hand, on the chip)
runs a ``--trace 1`` run of a cell with the events kept and prints it.
"""
import re

PANEL = re.compile(r"^jit_PANEL(\(|$)")
CALL_SPAN = "entry_call"


def chain_gaps(events):
    """[seconds between consecutive PANEL programs, summed] of each
    traced call of ``events`` that ran at least two; the panels of all
    chips in start order, cut into calls by the ``entry_call`` spans (a
    trace without them is one call)."""
    panels = sorted((s, s + d) for chip in events["chips"].values()
                    for name, s, d in chip["modules"] if PANEL.match(name))
    calls = [(s, e) for name, s, e in events["spans"] if name == CALL_SPAN] \
        or [(float("-inf"), float("inf"))]
    out = []
    for lo, hi in calls:
        mine = [(s, e) for s, e in panels if lo <= s < hi]
        if len(mine) > 1:
            out.append(sum(nxt[0] - prev[1]
                           for prev, nxt in zip(mine, mine[1:])) / 1e9)
    return out


def read(obs):
    events = (obs.get("trace") or {}).get("events")
    if not events:
        return None
    gaps = chain_gaps(events)
    return sum(gaps) / len(gaps) if gaps else None
