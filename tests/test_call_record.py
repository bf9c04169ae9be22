"""The call record that is always on (obs/phases.py ``root_span``), the
device manager's six always-on brackets behind it (devices/tpu.py), the
``chip_wait`` phase, and the benchmark's readers of the records
(perfbench/calls.py).  Counts and structure; the only times asserted
are of a wait the test itself makes sleep.
"""
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.obs import phases
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import calls  # noqa: E402

N, NB = 192, 48     # NT = 4; NB = 48: shapes no other test has, so that
                    # none finds its programs built (test_phase_clock)
FIELDS = {"wall_ns", "count"}
ENTRIES = {"dpotrf": ops.dpotrf, "dgeqrf": ops.dgeqrf,
           "dpotrf_dtd": ops.dpotrf_dtd, "dpoinv": ops.dpoinv}
NEW_METRICS = ("untraced_set_stage_s", "untraced_group_s",
               "untraced_dispatch_s", "untraced_epilog_s",
               "untraced_complete_s", "untraced_chip_wait_s",
               "span_inflation_pct",
               "slowest_wall_excess_s", "slowest_wall_chip_wait_s",
               "slowest_wall_unaccounted_s")


def _matrix(n=N):
    return TwoDimBlockCyclic(n, n, NB, NB, dtype=np.float32).from_numpy(
        ops.make_spd(n))


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _device_calls(stats):
    """Device calls made, counted without the brackets: stacked calls
    and tasks dispatched alone."""
    return stats["batches"] + stats["dispatch_tasks"] \
        - stats["batched_tasks"]


def _moved(dev, before):
    return {k: v - before[k] for k, v in dev.stats.items()
            if isinstance(v, (int, float))}


def _check_device_entry(entry, root_ns):
    """One device's six brackets: all there, disjoint (they add to no
    more than the root span); beside them the reshape engine's
    counters, still where nothing declares a type, and the tasks the
    device ran with the rule that placed each, and what it pulled from
    other chips."""
    assert set(entry) == set(phases.BRACKETS) | {"device", "reshape",
                                                 "placement", "stage",
                                                 "scratch", "peer"}
    assert set(entry["peer"]) == set(phases.PEER_COUNTERS)
    assert all(v >= 0 for v in entry["peer"].values())
    # a pull is counted, measured and its tile's bytes booked together
    assert bool(entry["peer"]["peer_pulls"]) \
        == bool(entry["peer"]["peer_pull_ns"])
    assert set(entry["scratch"]) == set(phases.SCRATCH_COUNTERS)
    # no host body made a buffer a device task read
    assert entry["scratch"]["scratch_stage_in_bytes"] == 0
    assert set(entry["stage"]) == set(phases.STAGE_COUNTERS)
    # small tiles: every set under the bound, a put a pass at most,
    # and none that the last wait's reading had to leave whole
    assert entry["stage"]["tasks_ahead_of_copy"] == 0
    assert entry["stage"]["sets_whole_by_wait"] == 0
    assert 0 < entry["stage"]["stage_chunks"] <= entry["set_stage"]["count"]
    assert set(entry["reshape"]) == set(phases.RESHAPE_COUNTERS)
    assert all(v >= 0 for v in entry["reshape"].values())
    assert set(entry["placement"]) == set(phases.PLACEMENT_COUNTERS)
    assert all(v >= 0 for v in entry["placement"].values())
    placed = sum(entry["placement"].values()) - entry["placement"]["tasks"]
    # one accelerator: nothing to decide, nothing counted
    assert placed in (0, entry["placement"]["tasks"])
    for b in phases.BRACKETS:
        e = entry[b]
        assert set(e) == FIELDS
        assert e["wall_ns"] >= 0 and e["count"] >= 0
    assert sum(entry[b]["wall_ns"] for b in phases.BRACKETS) <= root_ns


@pytest.fixture
def records():
    phases.clear_completed()
    yield phases.completed
    phases.clear_completed()


@pytest.fixture
def one_device_ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=3)
    yield c
    c.fini()


# ---------------------------------------------------------------- #
# every root call leaves a record                                  #
# ---------------------------------------------------------------- #
@pytest.mark.parametrize("op", sorted(ENTRIES))
def test_untraced_call_leaves_one_record(one_device_ctx, records, op):
    dev, = _accel(one_device_ctx)
    before = dict(dev.stats)
    ENTRIES[op](one_device_ctx, _matrix())
    rec, = records()
    assert rec["op"] == op and rec["traced"] is False
    assert "phases" not in rec and "by_thread" not in rec
    root = rec["t1_ns"] - rec["t0_ns"]
    assert root > 0
    assert set(rec["manager"]) == set(phases.BRACKETS)
    entry, = rec["by_device"]
    assert entry["device"] == dev.name
    _check_device_entry(entry, root)
    assert {b: entry[b] for b in rec["manager"]} == rec["manager"]
    moved = _moved(dev, before)
    count = {b: rec["manager"][b]["count"] for b in phases.BRACKETS}
    assert count["dispatch"] == _device_calls(moved) > 0
    assert rec["manager"]["dispatch"]["wall_ns"] == moved["dispatch_ns"]
    # every call filed is retired, waited for and completed once
    assert count["chip_wait"] == moved["retired_calls"] == count["dispatch"]
    assert count["epilog"] == count["complete"] == count["dispatch"]
    # one set pass and one grouping pass a chunk of a drained ready set
    assert count["set_stage"] == count["group"] >= 1
    if op == "dpoinv":
        assert [p["name"] for p in rec["parts"]] \
            == ["dpotrf_L", "dtrtri_L", "dlauum_L"]
        assert all(rec["t0_ns"] <= p["enqueued_ns"] <= p["first_call_ns"]
                   <= p["completed_ns"] <= rec["t1_ns"]
                   for p in rec["parts"])
        assert rec["compound_gap_ns"] > 0
    else:
        assert "parts" not in rec and "compound_gap_ns" not in rec
    report = phases.format_report(rec)
    assert "no phase clock" in report and "chip_wait" in report
    assert "in no bracket" in report and "release_deps" not in report
    assert ("part 2 dlauum_L" in report) == (op == "dpoinv")
    # nothing was switched on for it
    assert one_device_ctx._root_call is None
    assert one_device_ctx._phase_clock is None and dev._phases is None


def test_a_set_left_whole_by_the_last_wait_shows_in_the_record(
        one_device_ctx, records, monkeypatch):
    """``sets_whole_by_wait`` rides under ``by_device[*]["stage"]``
    beside the two counters it joins: the bound at two tiles, a manager
    whose last wait never waited for its chip (as the device's own
    counters say), and the call's record and report count the sets
    between the two bounds that went in one put."""
    from parsec_tpu.devices import tpu
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * NB * NB * 4)
    dev, = _accel(one_device_ctx)
    dev.stats["dispatch_ns"] += 190_000_000
    dev.stats["chip_wait_ns"] += 10_000_000
    dev.stats["retired_calls"] += 1
    dev.drain(one_device_ctx)
    before = dict(dev.stats)
    ops.dpotrf(one_device_ctx, _matrix())
    rec, = records()
    entry, = rec["by_device"]
    moved = _moved(dev, before)
    assert entry["stage"] == {c: moved[c] for c in phases.STAGE_COUNTERS}
    assert entry["stage"]["sets_whole_by_wait"] > 0
    assert f"{entry['stage']['sets_whole_by_wait']} sets over the bound " \
        "whole" in phases.format_report(rec)


def test_each_device_has_its_own_disjoint_brackets(ctx4, records):
    """Several accelerators in one context: ``by_device`` has each
    manager's six brackets, disjoint on that device, and ``manager``
    is their sum."""
    devs = _accel(ctx4)
    assert len(devs) > 1
    before = [dict(d.stats) for d in devs]
    ops.dpotrf(ctx4, _matrix(8 * NB))
    rec, = records()
    root = rec["t1_ns"] - rec["t0_ns"]
    assert [e["device"] for e in rec["by_device"]] == [d.name for d in devs]
    for entry, dev, was in zip(rec["by_device"], devs, before):
        _check_device_entry(entry, root)
        moved = _moved(dev, was)
        assert entry["dispatch"]["count"] == _device_calls(moved)
        assert entry["peer"] == {c: moved[c] for c in phases.PEER_COUNTERS}
    # a panel tile is read on other chips than the one that wrote it
    assert sum(e["peer"]["peer_pulls"] for e in rec["by_device"]) > 0
    for b, total in rec["manager"].items():
        for f in total:
            assert total[f] == sum(e[b][f] for e in rec["by_device"])
    assert sum(e["dispatch"]["count"] > 0 for e in rec["by_device"]) > 1


def test_nested_root_span_leaves_no_second_record(one_device_ctx, records):
    with phases.root_span(one_device_ctx, "outer", 7) as clock:
        assert clock is None
        ops.dpotrf(one_device_ctx, _matrix())
    rec, = records()
    assert rec["op"] == "outer" and rec["id"] == 7
    assert rec["manager"]["dispatch"]["count"] > 0


def test_a_call_that_raises_still_leaves_its_record(one_device_ctx, records):
    with pytest.raises(ZeroDivisionError):
        with phases.root_span(one_device_ctx, "broken", 3):
            1 / 0
    rec, = records()
    assert rec["op"] == "broken" and rec["t1_ns"] >= rec["t0_ns"]
    assert all(e["count"] == 0 for e in rec["manager"].values())
    assert one_device_ctx._root_call is None


# ---------------------------------------------------------------- #
# with a clock: the same block beside the phases, chip_wait a      #
# phase, one pair of stamps for both                               #
# ---------------------------------------------------------------- #
def test_profile_context_has_the_manager_block_beside_phases(records):
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        ops.dpotrf(c, _matrix())
        rec, = records()
    finally:
        c.fini()
    assert rec["traced"] is False and "chip_wait" in phases.PHASES
    got, mgr = rec["phases"], rec["manager"]
    _check_device_entry(rec["by_device"][0], rec["t1_ns"] - rec["t0_ns"])
    calls_made = mgr["dispatch"]["count"]
    assert calls_made > 0
    # _retire opens chip_wait and no epilog span: one epilog a call
    assert got["chip_wait"]["count"] == mgr["chip_wait"]["count"] \
        == calls_made
    assert got["epilog"]["count"] == mgr["epilog"]["count"] == calls_made
    # a site's two stamps feed its span and its bracket: where the span
    # has no child span, self time and bracket agree to the nanosecond
    assert got["chip_wait"]["self_ns"] == mgr["chip_wait"]["wall_ns"]
    assert got["epilog"]["self_ns"] == mgr["epilog"]["wall_ns"]
    assert sum(got.get(p, {}).get("self_ns", 0)
               for p in ("dispatch", "first_call")) \
        == mgr["dispatch"]["wall_ns"]
    assert sum(got.get(p, {}).get("count", 0)
               for p in ("dispatch", "first_call")) == calls_made
    report = phases.format_report(rec)
    assert "chip_wait" in report and "release_deps" in report
    assert "in no bracket" in report


def test_traced_call_writes_chip_wait_into_the_trace(one_device_ctx,
                                                     records, tmp_path):
    import jax
    from perfbench import xplane
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ops.dpotrf(one_device_ctx, _matrix())
    finally:
        jax.profiler.stop_trace()
    rec, = records()
    assert rec["traced"] is True
    assert set(rec["manager"]) == set(phases.BRACKETS)
    pd = xplane.load(xplane.find_xplane(str(tmp_path)))
    names = [ev.name for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for ev in line.events
             if ev.name.startswith("parsec:")]
    assert names.count("parsec:chip_wait") \
        == rec["manager"]["chip_wait"]["count"] > 0
    assert names.count("parsec:epilog") == rec["manager"]["epilog"]["count"]


# ---------------------------------------------------------------- #
# waiting for the chip is chip_wait, not epilog                    #
# ---------------------------------------------------------------- #
class _SleepingOutput:
    """A call's output whose kernel is still running: the wait for it
    sleeps, off the CPU."""
    NAP = 0.05

    def is_deleted(self):
        return False

    def is_ready(self):
        return False

    def block_until_ready(self):
        time.sleep(self.NAP)


@pytest.mark.parametrize("profile", [False, True])
def test_a_sleeping_kernel_shows_in_chip_wait_not_epilog(monkeypatch,
                                                         records, profile):
    record = JaxDevice._record

    def slow(self, chunk, outs, how, waits=None):
        rec = record(self, chunk, outs, how, waits)
        rec.waits = [[_SleepingOutput()]]
        return rec

    monkeypatch.setattr(JaxDevice, "_record", slow)
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=1, profile=profile)
    try:
        dev, = _accel(c)
        batches = dev.stats["batches"]
        ops.dpotrf(c, _matrix())
        stacked = dev.stats["batches"] - batches
        rec, = records()
    finally:
        c.fini()
    assert stacked >= 1
    slept = int(stacked * _SleepingOutput.NAP * 1e9)
    mgr = rec["manager"]
    assert mgr["chip_wait"]["wall_ns"] >= slept
    working = sum(mgr[b]["wall_ns"] for b in calls.WORKING)
    assert working + mgr["chip_wait"]["wall_ns"] \
        <= rec["t1_ns"] - rec["t0_ns"]
    assert mgr["epilog"]["wall_ns"] < slept
    if profile:
        assert rec["phases"]["chip_wait"]["self_ns"] >= slept
        assert rec["phases"]["epilog"]["self_ns"] < slept


# ---------------------------------------------------------------- #
# the benchmark's readers, against canned records                  #
# ---------------------------------------------------------------- #
def _canned(root_s, traced=False, devices=1, **bracket_s):
    """A record whose brackets hold ``bracket_s`` seconds of wall (on
    each of ``devices`` managers)."""
    def block(n):
        return {b: {"wall_ns": int(bracket_s.get(b, 0.0) * n * 1e9),
                    "count": 10 * n} for b in phases.BRACKETS}
    return {"op": "dpotrf", "id": 1, "t0_ns": 0, "t1_ns": int(root_s * 1e9),
            "traced": traced, "manager": block(devices),
            "by_device": [dict(block(1), device=f"tpu:{i}")
                          for i in range(devices)]}


PLAIN = dict(set_stage=0.1, group=0.2, dispatch=0.4, epilog=0.2,
             complete=0.1, chip_wait=0.05)
SPANNED = dict(set_stage=0.15, group=0.3, dispatch=0.5, epilog=0.3,
               complete=0.25, chip_wait=0.05)
#: the window: an untraced wall, two traced, two untraced of which the
#: last stalled for 3 s: 2 of them waiting for the chip, 0.4 in the
#: managers' Python, 0.6 in nobody's brackets
WALLS = [1.5, 2.0, 2.1, 1.5, 4.5]
STALLED = dict(PLAIN, chip_wait=2.05, group=0.6)
WANT = {"untraced_set_stage_s": 0.1,
        "untraced_group_s": (0.2 + 0.2 + 0.6) / 3,
        "untraced_dispatch_s": 0.4, "untraced_epilog_s": 0.2,
        "untraced_complete_s": 0.1,
        "untraced_chip_wait_s": (0.05 + 0.05 + 2.05) / 3,
        "span_inflation_pct": 100.0 * (1.5 / ((1.0 + 1.0 + 1.4) / 3) - 1),
        "slowest_wall_excess_s": 3.0, "slowest_wall_chip_wait_s": 2.0,
        "slowest_wall_unaccounted_s": 0.6}


def _window(devices=1):
    return [_canned(1.45, devices=devices, **PLAIN),
            _canned(1.95, traced=True, devices=devices, **SPANNED),
            _canned(2.05, traced=True, devices=devices, **SPANNED),
            _canned(1.45, devices=devices, **PLAIN),
            _canned(4.45, devices=devices, **STALLED)]


def _obs(**over):
    obs = {"walls": list(WALLS), "n_traced": 2, "n_counted": 5}
    obs.update(over)
    return obs


def _reader(name):
    path = os.path.join(ROOT, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", NEW_METRICS)
def test_call_record_metric_reader(records, name):
    read = _reader(name)
    # set-up's two calls come first: the readers take the window's
    phases._completed.extend([_canned(3.0, **PLAIN), _canned(1.4, **PLAIN)]
                             + _window())
    assert read(_obs()) == pytest.approx(WANT[name])
    # nothing rather than zero: walls that are not the records' (a
    # count that does not match, a root span outside its wall), a
    # window with no untraced call, a program without the records
    assert read(_obs(walls=[])) is None
    assert read(_obs(walls=WALLS + [1.5, 1.5, 1.5])) is None
    assert read(_obs(walls=[1.5, 2.0, 2.1, 1.5, 4.0])) is None   # root > wall
    assert read(_obs(walls=[1.5, 2.0, 2.1, 1.5, 6.0])) is None   # root < 80%
    phases.clear_completed()
    phases._completed.extend(_window()[1:3])        # both traced
    assert read(_obs(walls=WALLS[1:3])) is None
    phases.clear_completed()
    assert read(_obs()) is None
    # a record of the program before this one: no manager block
    old = [{k: v for k, v in r.items() if k not in ("manager", "by_device")}
           for r in _window()]
    phases._completed.extend(old)
    assert read(_obs()) is None


def test_readers_on_four_managers(records):
    """Summed over the managers per factorization; the slowest wall's
    parts use the mean over the managers, so they still add to its
    excess."""
    phases._completed.extend(_window(devices=4))
    assert calls.untraced_seconds(_obs(), "dispatch") == pytest.approx(1.6)
    slow = calls.slowest_wall(_obs())
    assert slow == pytest.approx({"excess_s": 3.0, "chip_wait_s": 2.0,
                                  "unaccounted_s": 0.6})


def test_slowest_wall_wants_two_untraced_calls(records):
    phases._completed.extend(_window()[:3])
    obs = _obs(walls=WALLS[:3])
    assert calls.untraced_seconds(obs, "group") == pytest.approx(0.2)
    assert calls.span_inflation_pct(obs) == pytest.approx(50.0)
    assert calls.slowest_wall(obs) is None


def test_benchmark_lists_the_new_metrics():
    """Every cell reports the seven that one untraced call of a traced
    window feeds; the slowest wall's three want two, which the traced
    windows of the DTD, LU and dpoinv cells do not always hold."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW_METRICS}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["moves"] == "factor_s"
        assert m["workloads"] == (cells[:4] if name.startswith("slowest_")
                                  else cells)
        assert m["layer"] in layers and m["better"] == "lower"
        assert callable(_reader(name))
    # appended, in order, after what the benchmark had (later PRs
    # append after them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert first >= 62
    assert names[first:first + len(NEW_METRICS)] == list(NEW_METRICS)
