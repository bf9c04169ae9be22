"""The phase clock (obs/phases.py), what switches it on, the program
names and always-on counters of the device module, and the benchmark's
readers of all of it.  Counts and structure only: no time is asserted.
"""
import importlib.util
import os
import sys
import threading
import types

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.devices import batching
from parsec_tpu.obs import phases
from parsec_tpu.profiling.pins import pins_is_active
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, NB = 128, 32     # NT = 4


def _dpotrf_tasks(nt):
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


def _dgeqrf_tasks(nt):
    return sum(1 + 2 * (nt - 1 - k) + (nt - 1 - k) ** 2 for k in range(nt))


OPS = {"dpotrf": (ops.dpotrf, _dpotrf_tasks(N // NB)),
       "dgeqrf": (ops.dgeqrf, _dgeqrf_tasks(N // NB))}


def _matrix():
    return TwoDimBlockCyclic(N, N, NB, NB, dtype=np.float32).from_numpy(
        ops.make_spd(N))


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


@pytest.fixture
def one_device_ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=3)
    yield c
    c.fini()


@pytest.fixture
def one_worker_ctx():
    """One worker: a class's ready tasks reach the manager together, so
    two runs of one DAG dispatch the same buckets."""
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=1)
    yield c
    c.fini()


@pytest.fixture
def session(tmp_path):
    """A recording JAX profiler session; yields the directory."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    phases.clear_completed()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    stopped = []

    def stop():
        if not stopped:
            stopped.append(True)
            jax.profiler.stop_trace()
    try:
        yield types.SimpleNamespace(dir=str(tmp_path), stop=stop)
    finally:
        stop()
        phases.clear_completed()


# ---------------------------------------------------------------- #
# (a) one record per call under a session; the books balance       #
# ---------------------------------------------------------------- #
@pytest.mark.parametrize("op", sorted(OPS))
def test_record_under_profiler_session(one_device_ctx, session, op):
    entry, n_tasks = OPS[op]
    entry(one_device_ctx, _matrix())
    records = phases.completed()
    assert len(records) == 1
    rec = records[0]
    assert rec["op"] == op and rec["traced"] is True
    root = rec["t1_ns"] - rec["t0_ns"]
    assert root > 0 and rec["caller_thread"] in rec["by_thread"]
    for name, th in rec["by_thread"].items():
        assert th["other_ns"] >= 0, (name, th)
        assert sum(e["self_ns"] for e in th["phases"].values()) \
            + th["other_ns"] == root, name
        assert all(e["self_ns"] >= 0 for e in th["phases"].values()), name
    # over threads: phases + other == threads x root span
    assert sum(e["self_ns"] for e in rec["phases"].values()) \
        == len(rec["by_thread"]) * root
    assert set(rec["phases"]) <= set(phases.PHASES)
    assert rec["phases"]["complete"]["count"] == n_tasks
    assert rec["phases"]["release_deps"]["count"] == n_tasks
    assert rec["phases"]["exec"]["count"] == n_tasks
    dispatched = sum(rec["phases"].get(p, {}).get("tasks", 0)
                     for p in ("dispatch", "first_call"))
    assert dispatched == n_tasks
    # a fresh taskpool's programs are all first calls somewhere
    assert rec["phases"]["first_call"]["count"] >= 1
    assert "stage_in" in rec["phases"] and "manager" in rec["phases"]
    report = phases.format_report(rec)
    assert op in report and "release_deps" in report and "other" in report
    # the switch is back off: the PINS fast path, the device sites
    assert not pins_is_active()
    assert all(d._phases is None for d in one_device_ctx.devices)
    assert one_device_ctx._phase_clock is None


def test_record_with_profile_switch_and_chrome_export():
    """``Context(profile=True)`` alone (no session) makes records too,
    flagged untraced, and the Chrome export carries the phase rows
    beside the exec spans it always had."""
    from parsec_tpu.obs import validate_chrome_trace
    phases.clear_completed()
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        ops.dpotrf(c, _matrix())
        rec, = phases.completed()
        assert rec["traced"] is False
        assert rec["phases"]["complete"]["count"] == _dpotrf_tasks(N // NB)
        doc = c.profile.to_chrome_trace()
        validate_chrome_trace(doc)
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert "exec:GEMM" in names and "phase:release_deps" in names
        assert "phase:dispatch" in names or "phase:first_call" in names
    finally:
        c.fini()
        phases.clear_completed()


# ---------------------------------------------------------------- #
# (b) no session, no profile switch: nothing is switched on, and   #
# the call's record holds its stamps and the manager block alone   #
# ---------------------------------------------------------------- #
def test_no_session_no_record(one_device_ctx, monkeypatch):
    phases.clear_completed()
    seen = []
    wait = type(one_device_ctx).wait

    def spying_wait(self):
        seen.append((pins_is_active(), self._phase_clock,
                     [d._phases for d in self.devices],
                     [d._obs for d in self.devices]))
        return wait(self)
    monkeypatch.setattr(type(one_device_ctx), "wait", spying_wait)
    assert not phases.session_recording()
    ops.dpotrf(one_device_ctx, _matrix())
    active, clock, dev_phases, dev_obs = seen[0]
    assert active is False and clock is None
    assert all(p is None for p in dev_phases)
    assert all(o is None for o in dev_obs)
    rec, = phases.completed()
    assert rec["traced"] is False and rec["op"] == "dpotrf"
    assert "phases" not in rec and "by_thread" not in rec
    assert set(rec["manager"]) == set(phases.BRACKETS)
    assert all(d._obs is None for d in one_device_ctx.devices)
    assert one_device_ctx._root_call is None
    phases.clear_completed()


# ---------------------------------------------------------------- #
# (c) the spans are in the profiler's own trace, nested            #
# ---------------------------------------------------------------- #
def test_spans_in_xplane_nested_in_callers_annotation(one_device_ctx,
                                                      session):
    import jax
    sys.path.insert(0, ROOT)
    from perfbench import xplane
    with jax.profiler.TraceAnnotation("test:outer"):
        ops.dpotrf(one_device_ctx, _matrix())
    session.stop()
    pd = xplane.load(xplane.find_xplane(session.dir))
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    assert host
    by_line = []
    for line in host[0].lines:
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats)) for ev in line.events
                 if ev.name.startswith(("parsec:", "test:"))]
        if spans:
            by_line.append(spans)
    names = {n for spans in by_line for n, _, _, _ in spans}
    for want in ("parsec:op", "parsec:exec", "parsec:complete",
                 "parsec:release_deps", "parsec:manager", "parsec:stage_in",
                 "parsec:epilog", "parsec:chip_wait",
                 "parsec:prepare_input"):
        assert want in names, (want, sorted(names))
    assert names & {"parsec:dispatch", "parsec:first_call"}
    # idle workers' microsecond spans are booked, not annotated
    assert not names & {"parsec:" + p for p in phases.UNANNOTATED}
    caller, = [spans for spans in by_line
               if any(n == "test:outer" for n, _, _, _ in spans)]
    (_, o0, o1, _), = [s for s in caller if s[0] == "test:outer"]
    (_, r0, r1, root_args), = [s for s in caller if s[0] == "parsec:op"]
    assert o0 <= r0 and r1 <= o1
    assert root_args["op"] == "dpotrf"
    inside = [s for s in caller if s[0].startswith("parsec:")
              and s[0] != "parsec:op"]
    assert inside and all(r0 <= s0 and s1 <= r1 for _, s0, s1, _ in inside)
    # every span of the call carries the request's id
    assert {a["id"] for spans in by_line for n, _, _, a in spans
            if n.startswith("parsec:")} == {root_args["id"]}
    disp = [a for spans in by_line for n, _, _, a in spans
            if n == "parsec:dispatch"]
    assert all("cls" in a and "n" in a for a in disp)


# ---------------------------------------------------------------- #
# (d) every dispatched program is named for its task class         #
# ---------------------------------------------------------------- #
def _spec(name="GEMM[tpu]"):
    return batching.DeviceBatchSpec(
        name, lambda task, arrays: None,
        lambda bargs, static: (bargs[0] + bargs[1],))


@pytest.mark.parametrize("spec_name,n,want", [
    ("GEMM[tpu]", 16, "GEMM_x16"), ("GEMM[tpu]", 1, "GEMM"),
    ("TSQRT[tpu]", 2, "TSQRT_x2"), ("scale", 4, "scale_x4"),
    ("my-kernel", 1, "my_kernel")])
def test_program_name(spec_name, n, want):
    assert batching.program_name(spec_name, n) == want


@pytest.mark.parametrize("n", [1, 2, 4, 16])
def test_stacked_program_carries_class_name(n):
    import jax.numpy as jnp
    prog = batching.build_stacked_callable(_spec(), n, 2, ())
    want = "GEMM" if n == 1 else f"GEMM_x{n}"
    assert prog.name == want
    x = jnp.ones((4, 4), jnp.float32)
    flat = [x] * (2 * n)
    assert prog.fn.lower(*flat).as_text().startswith(
        f"module @jit_{want} ")
    outs = prog(*flat)
    assert len(outs) == n
    np.testing.assert_array_equal(np.asarray(outs[0]), 2 * np.ones((4, 4)))
    assert prog.first_call_on("tpu:0") and not prog.first_call_on("tpu:0")
    assert prog.first_call_on("tpu:1")


def test_sharded_program_carries_class_name():
    import jax
    import jax.numpy as jnp
    from parsec_tpu.parallel.mesh import make_mesh
    chips = jax.devices("cpu")[:4]
    mesh = make_mesh(sizes={"tp": 2, "sp": 2}, devices=chips)
    shapes = (((4, 4), "float32"),) * 2
    prog = batching.build_sharded_callable(_spec("SYRK[tpu]"), 4, 2, (),
                                           shapes, mesh)
    assert prog.name == "SYRK_x4" and prog.n_out == 1
    g = jax.device_put(jnp.ones((4, 4, 4), jnp.float32), prog.sharding)
    assert prog.fn.lower(g, g).as_text().startswith("module @jit_SYRK_x4 ")
    out, = prog(g, g)
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 4, 4)))


def _programs(tp):
    """name -> program, of every stacked program ``tp`` dispatched: a
    body that can say what it reads keeps them under its token."""
    progs = {}
    for tc in tp.task_classes:
        for chore in tc.incarnations:
            spec = getattr(chore, "batch_spec", None)
            if spec is not None and spec.cache_token is not None:
                assert not spec.cache
                progs.update((prog.name, prog) for prog in
                             batching._shared_cache[spec.cache_token].values())
    return progs


def test_names_equal_across_fresh_taskpools(no_programs, one_worker_ctx):
    from parsec_tpu.ops import linalg
    seen = []
    for _ in range(2):
        tp = ops.dpotrf_taskpool(_matrix())
        one_worker_ctx.add_taskpool(tp)
        one_worker_ctx.wait()
        seen.append(_programs(tp))
    assert seen[0] == seen[1] and seen[0]   # the very same programs
    assert all("_x" in n for n in seen[0])
    assert {n.split("_x")[0] for n in seen[0]} <= {"TRSM", "SYRK", "GEMM"}
    # a task dispatched alone ran its kernel under the class's name:
    # one clone per (class, kernel) in the process, whatever the taskpool
    potrf = batching._class_kernels[("POTRF", linalg.potrf)]
    x = np.eye(4, dtype=np.float32)
    assert potrf.lower(x).as_text().startswith("module @jit_POTRF ")
    assert all(kernel is not linalg.potrf or cls == "POTRF"
               for cls, kernel in batching._class_kernels)


def test_kernels_named_for_a_class():
    import functools
    import jax
    import jax.numpy as jnp
    mod = types.SimpleNamespace(
        plain=jax.jit(lambda a, b: a + b),
        scaled=functools.partial(jax.jit, static_argnames=("k",))(
            lambda a, k: a * k),
        helper=len, const=3)
    named = batching.KernelsNamedFor(mod, "TSQRT[tpu]")
    x = jnp.ones((2, 2), jnp.float32)
    assert named.helper is len and named.const == 3
    assert named.plain is named.plain             # built once
    assert named.plain is not mod.plain
    assert named.plain.lower(x, x).as_text().startswith("module @jit_TSQRT ")
    np.testing.assert_array_equal(np.asarray(named.plain(x, x)), 2 * np.ones((2, 2)))
    # static arguments stay static in the clone
    np.testing.assert_array_equal(np.asarray(named.scaled(x, k=3)),
                                  3 * np.ones((2, 2)))
    assert batching.KernelsNamedFor(mod, "GEQRT").plain is not named.plain
    with pytest.raises(AttributeError):
        named.missing


# ---------------------------------------------------------------- #
# (e) the always-on counters                                       #
# ---------------------------------------------------------------- #
def test_first_calls_move_once_per_process(no_programs, one_worker_ctx):
    """(Was test_first_calls_move_on_a_fresh_taskpool: every taskpool
    rebuilt its programs.)"""
    dev, = _accel(one_worker_ctx)
    before = dict(dev.stats)
    ops.dpotrf(one_worker_ctx, _matrix())
    mid = dict(dev.stats)
    ops.dpotrf(one_worker_ctx, _matrix())
    after = dict(dev.stats)
    calls = mid["first_calls"] - before["first_calls"]
    assert calls >= 1
    assert mid["first_call_ns"] > before["first_call_ns"]
    # a part of dispatch_ns, never more
    assert mid["first_call_ns"] - before["first_call_ns"] \
        <= mid["dispatch_ns"] - before["dispatch_ns"]
    # one program per (class, bucket): far fewer than tasks
    assert calls < mid["tasks"] - before["tasks"]
    assert mid["program_reuse"] == before["program_reuse"]
    # the second taskpool's stacked dispatches all found their program
    assert after["first_calls"] == mid["first_calls"]
    assert after["first_call_ns"] == mid["first_call_ns"]
    assert after["program_reuse"] - mid["program_reuse"] \
        == after["batches"] - mid["batches"] >= 1
    assert after["stage_in_bytes"] > 0
    assert after["stage_in_peer_bytes"] == 0
    # counters need no session, and no record has a phase table
    assert not any("phases" in r for r in phases.completed())


def test_stage_in_peer_bytes_counts_chip_to_chip(ctx4):
    """With several accelerator devices in one context a tile a task
    only reads is pulled to the chip that needs it, from the chip that
    wrote it: those pulls are the peer part of stage-in."""
    devs = _accel(ctx4)
    assert len(devs) > 1
    n, nb = 256, 32
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(
        ops.make_spd(n))
    ops.dpotrf(ctx4, A)
    total = sum(d.stats["stage_in_bytes"] for d in devs)
    peer = sum(d.stats["stage_in_peer_bytes"] for d in devs)
    assert 0 <= peer <= total
    if sum(1 for d in devs if d.stats["tasks"]) > 1:
        assert peer > 0


# ---------------------------------------------------------------- #
# the clock itself                                                 #
# ---------------------------------------------------------------- #
def test_self_time_nesting_rename_and_unmatched_ends():
    clock = phases.PhaseClock("unit", 7, traced=False)
    clock.pop("exec")                   # an end with no begin: ignored
    clock.push("complete", cls="X")
    clock.push("release_deps")
    clock.push("schedule", n=3)
    clock.pop("schedule")
    clock.pop("release_deps")
    clock.pop("complete")
    clock.push("select")
    clock.pop("select", "idle_poll")
    clock.push("dispatch")
    clock.pop("dispatch", "first_call", tasks=4)
    clock.push("prepare_input")         # its end is skipped ...
    clock.push("exec")
    clock.pop("prepare_input")          # ... so this closes both
    clock.push("parked")                # still open at close
    rec = clock.close()
    clock.pop("parked")                 # after close: ignored
    clock.push("exec")
    assert rec["op"] == "unit" and rec["id"] == 7 and not rec["traced"]
    mine = rec["by_thread"][rec["caller_thread"]]
    counts = {k: v["count"] for k, v in mine["phases"].items()}
    assert counts == {"complete": 1, "release_deps": 1, "schedule": 1,
                      "idle_poll": 1, "first_call": 1, "prepare_input": 1,
                      "exec": 1, "parked": 1}
    assert mine["phases"]["first_call"]["tasks"] == 4
    root = rec["t1_ns"] - rec["t0_ns"]
    assert sum(e["self_ns"] for e in mine["phases"].values()) \
        + mine["other_ns"] == root
    assert mine["other_ns"] >= 0
    assert all(e["self_ns"] >= 0 for e in mine["phases"].values())


def test_clock_books_balance_under_thread_contention():
    """More threads than cores, a short switch interval, the root span
    closing while they still push and pop: every thread's books must
    still add up to exactly the root span."""
    clock = phases.PhaseClock("stress", 1, traced=False)
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            clock.push("complete")
            clock.push("release_deps")
            clock.pop("release_deps")
            clock.pop("complete")
            clock.push("select")
            clock.pop("select", "idle_poll")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=worker, name=f"w{i}")
               for i in range(2 * (os.cpu_count() or 4))]
    try:
        for t in threads:
            t.start()
        threading.Event().wait(0.3)
        rec = clock.close()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    root = rec["t1_ns"] - rec["t0_ns"]
    assert len(rec["by_thread"]) == len(threads) + 1   # + the caller
    for name, th in rec["by_thread"].items():
        assert th["other_ns"] >= 0, name
        assert sum(e["self_ns"] for e in th["phases"].values()) \
            + th["other_ns"] == root, name
    assert rec["phases"]["release_deps"]["count"] > 0


def test_completed_is_bounded():
    phases.clear_completed()
    cap = phases._COMPLETED_MAX
    assert cap >= 64        # a window's calls and set-up's two fit
    for i in range(cap + 6):
        phases._completed.append(phases.PhaseClock("x", i, False).close())
    got = phases.completed()
    assert len(got) == cap and got[0]["id"] == 6 \
        and got[-1]["id"] == cap + 5
    phases.clear_completed()


# ---------------------------------------------------------------- #
# (f) the benchmark's readers, against canned input                #
# ---------------------------------------------------------------- #
def _reader(name):
    sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _record(root_s, traced=True, **phase_s):
    per = {k: {"self_ns": int(v * 1e9), "count": 1}
           for k, v in phase_s.items()}
    return {"op": "dpotrf", "id": 1, "t0_ns": 0, "t1_ns": int(root_s * 1e9),
            "traced": traced, "phases": per, "caller_thread": "MainThread",
            "by_thread": {"MainThread": {"phases": {},
                                         "other_ns": int(0.1 * root_s * 1e9)},
                          "parsec-es1": {"phases": {}, "other_ns": 0}}}


ALL_PHASES = dict(select=0.1, prepare_input=0.2, exec=0.3, schedule=0.4,
                  complete=0.5, release_deps=0.6, idle_poll=0.7,
                  manager=0.8, epilog=0.9, stage_in=1.1, parked=5.0)
MODULES = {"jit_GEMM_x16(1)": 0.4, "jit_GEMM_x2(2)": 0.1, "jit_GEMM(3)": 0.1,
           "jit_SYRK_x8(4)": 0.2, "jit_TRSM_x4(5)": 0.3, "jit_POTRF(6)": 0.05,
           "jit_GEQRT(7)": 0.01, "jit_UNMQR_x2(8)": 0.02,
           "jit_TSQRT(9)": 0.7, "jit_TSMQR_x16(10)": 0.9,
           "jit_TSMQR_extra(11)": 9.0, "jit_stacked(12)": 9.0}


def _obs(**over):
    obs = {"n_traced": 2, "n_counted": 4, "walls": [2.0, 2.0, 2.1, 2.0],
           "counters": {"first_call_ns": 2_000_000_000,
                        "stage_in_peer_bytes": 8_000_000_000},
           "trace": {"modules_s": dict(MODULES)}}
    obs.update(over)
    return obs


SPAN_METRICS = {"sched_s": 1.0, "release_s": 1.1, "idle_poll_s": 0.7,
                "manager_s": 1.7, "stage_in_s": 1.1,
                "host_unattributed_pct": 10.0}
COUNTER_METRICS = {"first_call_s": 0.5, "stage_in_peer_gb": 2.0}
CLASS_METRICS = {"potrf_device_s": 0.025, "trsm_device_s": 0.15,
                 "syrk_device_s": 0.1, "gemm_device_s": 0.3,
                 "geqrt_device_s": 0.005, "unmqr_device_s": 0.01,
                 "tsqrt_device_s": 0.35, "tsmqr_device_s": 0.45}


@pytest.fixture
def canned_records():
    phases.clear_completed()
    phases._completed.extend([
        _record(1.0, traced=False, **ALL_PHASES),   # a profile=True call
        _record(1.9, **ALL_PHASES), _record(2.0, **ALL_PHASES)])
    yield
    phases.clear_completed()


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_reader(canned_records, name):
    read = _reader(name).read
    assert read(_obs()) == pytest.approx(SPAN_METRICS[name])
    # nothing rather than zero: an untraced run, a record count that
    # is not the traced count, a root span outside its wall
    assert read(_obs(n_traced=0)) is None
    assert read(_obs(n_traced=3, walls=[2.0] * 4)) is None
    assert read(_obs(walls=[2.0, 1.5, 2.1, 2.0])) is None      # root > wall
    assert read(_obs(walls=[2.0, 2.0, 2.6, 2.0])) is None      # root < 80%
    assert read(_obs(walls=[2.0])) is None
    phases.clear_completed()
    assert read(_obs()) is None


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_metric_reader(name):
    mod = _reader(name)
    assert mod.read(_obs()) == pytest.approx(COUNTER_METRICS[name])
    assert mod.read(_obs(counters={"dispatch_ns": 5})) is None  # the parent
    assert mod.read(_obs(n_counted=0)) is None
    assert getattr(mod, "COUNT", False) == (name == "stage_in_peer_gb")


@pytest.mark.parametrize("name", sorted(CLASS_METRICS))
def test_class_device_metric_reader(name):
    read = _reader(name).read
    assert read(_obs()) == pytest.approx(CLASS_METRICS[name])
    assert read(_obs(trace=None)) is None
    assert read(_obs(n_traced=0)) is None
    # a program that names no class (the parent's jit_stacked)
    assert read(_obs(trace={"modules_s": {"jit_stacked(1)": 1.0}})) is None


def test_benchmark_lists_every_new_reader():
    """A reader of one task class's programs is listed for the cells
    whose operation file names the class, and for no other: the
    families are what ``BENCHMARK.json`` and the operation files say,
    whatever cells a later PR adds."""
    from perfbench import spec
    bench = spec.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in list(SPAN_METRICS) + list(COUNTER_METRICS):
        assert per_layer[name]["workloads"] == cells, name
        assert per_layer[name]["moves"] == "factor_s"
    classes = {c: spec.Cell(bench, c).op["kernels"] for c in cells}
    assert all(classes.values())

    def family(cls):
        return [c for c in cells if cls in classes[c]]

    known = {k for ks in classes.values() for k in ks}
    assert set(CLASS_METRICS) <= set(per_layer)
    read = 0
    for name, m in per_layer.items():
        cls, _, kind = name.rpartition("_device_s" if name.endswith(
            "_device_s") else "_roofline")
        if not cls or cls.upper() not in known:
            continue    # no reader of one class's programs
        assert m["workloads"] == family(cls.upper()), name
        assert m["source"] == "device_trace" and m["moves"] == "factor_s"
        read += 1
    assert read >= len(CLASS_METRICS) + 2 * (8 + 3)
    # dpoinv's first part is dpotrf's DAG, under dpotrf's class names;
    # the one-class product and the mixed-precision Cholesky share GEMM
    assert len(family("GEMM")) > len(family("POTRF")) > len(family("TRTRI"))
    inverse = family("TRTRI")
    assert inverse and len(family("PANEL")) >= 2 and family("GEQRT")
    for name in ("compound_gap_s", "part_potrf_s", "part_trtri_s",
                 "part_lauum_s"):
        assert per_layer[name]["workloads"] == inverse, name
        assert per_layer[name]["source"] == "program_span"


# ---------------------------------------------------------------- #
# perfbench/checks/idle_by_phase.py on a hand-made trace           #
# ---------------------------------------------------------------- #
def _idle_tool():
    sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "perfbench", "checks", "idle_by_phase.py")
    spec = importlib.util.spec_from_file_location("idle_by_phase", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_idle_tool_innermost_and_overlap():
    tool = _idle_tool()
    spans = [("a", 10.0, 90.0), ("b", 20.0, 40.0), ("c", 25.0, 30.0),
             ("d", 95.0, 120.0)]
    pieces = tool.innermost(spans, 0.0, 100.0)
    assert pieces == [(0.0, 10.0, "outside_spans"), (10.0, 20.0, "a"),
                      (20.0, 25.0, "b"), (25.0, 30.0, "c"),
                      (30.0, 40.0, "b"), (40.0, 90.0, "a"),
                      (90.0, 95.0, "outside_spans"), (95.0, 100.0, "d")]
    got = tool.overlap(pieces, [(0.0, 22.0), (28.0, 50.0)])
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "outside_spans": 10.0, "a": 20.0, "b": 12.0, "c": 2.0}


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_idle_tool_attributes_gaps_to_spans():
    tool = _idle_tool()
    from perfbench import xplane
    ms = 1e6
    caller = [_ev("perfbench:traced", 0, 100 * ms),
              _ev("perfbench:entry_call", 10 * ms, 80 * ms),
              _ev("parsec:op", 12 * ms, 70 * ms),
              _ev("parsec:first_call", 20 * ms, 30 * ms)]
    worker = [_ev("parsec:parked", 0, 60 * ms),
              _ev("parsec:release_deps", 60 * ms, 20 * ms)]
    device = [_ev("fusion", 50 * ms, 10 * ms), _ev("fusion", 80 * ms, 5 * ms)]
    pd = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            types.SimpleNamespace(name="python", events=caller),
            types.SimpleNamespace(name="python", events=worker)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name="XLA Ops", events=device),
            types.SimpleNamespace(name="XLA Modules", events=[])])])
    r = tool.attribute(pd, xplane)
    assert r["chips"] == 1 and r["host_threads"] == 2
    assert r["root_spans_inside_entry_call"] == 1
    assert r["idle_s"] == pytest.approx(0.085)
    # idle: [0,50] [60,80] [85,100] ms
    assert r["caller"]["parsec:first_call"] == pytest.approx(0.030)
    assert sum(r["caller"].values()) == pytest.approx(0.085)
    assert sum(r["mean"].values()) == pytest.approx(0.085)
    assert r["any"]["parsec:release_deps"] == pytest.approx(0.020)
    # nobody: [0,12] before the call and [85,100] after it, less nothing
    assert r["any"]["nobody"] == pytest.approx(0.012 + 0.015)
    assert "parsec:first_call" in tool.table(r)
