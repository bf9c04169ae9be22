"""MCA parameter system tests (ref: parsec/utils/mca_param.c behavior)."""
import os

import pytest

from parsec_tpu.utils.params import ParamRegistry


@pytest.fixture
def reg():
    return ParamRegistry()


def test_default_resolution(reg):
    reg.reg_int("x", 7)
    assert reg.get("x") == 7
    assert reg.source("x") == "default"


def test_env_overrides_default(reg, monkeypatch):
    reg.reg_int("window", 100)
    monkeypatch.setenv("PARSEC_MCA_window", "42")
    assert reg.get("window") == 42
    assert reg.source("window") == "env"


def test_cmdline_overrides_env(reg, monkeypatch):
    reg.reg_string("sched", "lfq")
    monkeypatch.setenv("PARSEC_MCA_sched", "gd")
    rest = reg.parse_argv(["prog", "--mca", "sched", "ap", "positional"])
    assert rest == ["prog", "positional"]
    assert reg.get("sched") == "ap"
    assert reg.source("sched") == "cmdline"


def test_parse_argv_forms(reg):
    reg.reg_int("a", 0)
    reg.reg_int("b", 0)
    rest = reg.parse_argv(["--mca=a=1", "--parsec", "b=2", "keep"])
    assert rest == ["keep"]
    assert reg.get("a") == 1 and reg.get("b") == 2


def test_typed_coercion(reg, monkeypatch):
    reg.reg_bool("flag", False)
    reg.reg_sizet("sz", 0)
    monkeypatch.setenv("PARSEC_MCA_flag", "yes")
    monkeypatch.setenv("PARSEC_MCA_sz", "0x100")
    assert reg.get("flag") is True
    assert reg.get("sz") == 256


def test_sizet_rejects_negative(reg):
    reg.reg_sizet("n", 0)
    reg.set_cmdline("n", "-5")
    with pytest.raises(ValueError):
        reg.get("n")


def test_unknown_param_raises(reg):
    with pytest.raises(KeyError):
        reg.get("nope")


def test_get_cmdline_public_accessor(reg):
    """The public cmdline-layer accessor (ADVICE r5: embedders must not
    reach into params._cmdline)."""
    reg.reg_string("s", "default")
    assert reg.get_cmdline("s") is None
    reg.set_cmdline("s", "v1")
    assert reg.get_cmdline("s") == "v1"
    reg.unset_cmdline("s")
    assert reg.get_cmdline("s") is None


def test_cmdline_override_contextmanager(reg):
    reg.reg_string("s", "default")
    with reg.cmdline_override("s", "inner"):
        assert reg.get("s") == "inner"
    assert reg.get("s") == "default"
    assert reg.get_cmdline("s") is None
    # restores a pre-existing override instead of popping it
    reg.set_cmdline("s", "outer")
    with reg.cmdline_override("s", "inner"):
        assert reg.get("s") == "inner"
    assert reg.get("s") == "outer"
    # exception-safe
    with pytest.raises(RuntimeError):
        with reg.cmdline_override("s", "inner"):
            raise RuntimeError("boom")
    assert reg.get("s") == "outer"


def test_cmdline_override_concurrent_same_name(reg):
    """Regression (ISSUE 16 satellite): overlapping same-name overrides
    from concurrent threads — the spmd rank-thread pattern every
    multi-rank test uses — must unwind cleanly.  The old save/restore
    implementation captured the OTHER thread's in-flight value as its
    "previous" layer and re-published it on exit, leaking a stale
    cmdline override into whichever test ran next (the test_stagec →
    test_overlap_pipeline ordering flake)."""
    import threading

    reg.reg_string("s", "default")
    start = threading.Barrier(8)
    errs = []

    def worker(i):
        try:
            start.wait(timeout=30)
            for j in range(200):
                with reg.cmdline_override("s", f"t{i}.{j}"):
                    # any thread's in-flight value is legal here; the
                    # invariant under test is the unwind below
                    assert reg.get("s") != "default"
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs
    # every layer unwound: no override survives the stampede
    assert reg.get_cmdline("s") is None
    assert reg.get("s") == "default"


def test_stagec_then_overlap_pipeline_ordering():
    """Regression (ISSUE 16 satellite): the historical failing order —
    ``test_stagec.py`` before ``test_overlap_pipeline.py`` in ONE
    interpreter — must stay green.  The flake was a stale cmdline
    override leaked by concurrent same-name ``cmdline_override`` exits
    (see test_cmdline_override_concurrent_same_name); a file pair in a
    fresh subprocess pins the end-to-end symptom, not just the
    mechanism."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         os.path.join("tests", "test_stagec.py"),
         os.path.join("tests", "test_overlap_pipeline.py")],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-500:]


def test_file_values(reg, tmp_path, monkeypatch):
    conf = tmp_path / "mca.conf"
    conf.write_text("# comment\nfoo = 13\n")
    monkeypatch.setenv("PARSEC_SYSCONF_PARAMS", str(conf))
    reg.reg_int("foo", 1)
    assert reg.get("foo") == 13
    assert reg.source("foo") == "file"


def test_thread_binding_param():
    """bind_threads MCA param (ref: --parsec_bind / bindthread.c)."""
    import os
    import parsec_tpu
    from parsec_tpu.runtime.vpmap import binding_for, bind_current_thread

    parsec_tpu.params.reset()
    assert binding_for(0, 4) is None  # off by default
    allowed = sorted(os.sched_getaffinity(0))
    parsec_tpu.params.set_cmdline("bind_threads", "rr")
    try:
        assert binding_for(0, 4) == allowed[0]
        assert binding_for(1, 4) == allowed[1 % len(allowed)]
        parsec_tpu.params.set_cmdline("bind_threads",
                                      f"{allowed[0]},{allowed[-1]}")
        assert binding_for(0, 2) == allowed[0]
        assert binding_for(1, 2) == allowed[-1]
        # binding the calling thread really takes effect and is undoable
        before = os.sched_getaffinity(0)
        try:
            assert bind_current_thread(allowed[0])
            assert os.sched_getaffinity(0) == {allowed[0]}
        finally:
            os.sched_setaffinity(0, before)
    finally:
        parsec_tpu.params.reset()


def test_workers_bound_when_enabled():
    """Every ES of a bind_threads=rr context sees its own deterministic
    core, and the locality helpers consume exactly that binding.

    Deliberately NOT asserted on real OS affinity of a worker thread:
    whether worker 1 ever wins a task off the scheduler is a race (the
    keep-highest-priority bypass lets the inserting thread eat small
    DAGs whole), which made the old probe-task version flaky.  The
    effect of ``bind_current_thread`` on the calling thread is already
    covered by test_thread_binding_param; here we pin down the
    per-worker core ASSIGNMENT and the scheduler-visible view of it
    (the ``_topo_binding_override`` hook models the same contract in
    test_topology.py)."""
    import parsec_tpu
    import os
    allowed = sorted(os.sched_getaffinity(0))
    if len(allowed) < 2:
        import pytest
        pytest.skip("needs >= 2 allowed cores")
    parsec_tpu.params.reset()
    parsec_tpu.params.set_cmdline("bind_threads", "rr")
    try:
        from parsec_tpu.runtime.vpmap import binding_for
        from parsec_tpu.sched.modules import _es_core
        ctx = parsec_tpu.Context(nb_cores=2, enable_tpu=False)
        try:
            for es in ctx.execution_streams:
                expect = allowed[es.th_id % len(allowed)]
                assert binding_for(es.th_id, ctx.nb_cores) == expect
                assert _es_core(es) == expect
            # and the override hook takes precedence over the computed
            # binding — the deterministic seam the topology tests use
            ctx._topo_binding_override = {es.th_id: allowed[0]
                                          for es in ctx.execution_streams}
            assert all(_es_core(es) == allowed[0]
                       for es in ctx.execution_streams)
        finally:
            ctx.fini()
    finally:
        parsec_tpu.params.reset()


# --------------------------------------------------------------------- #
# MCA component repository (ref: parsec/mca/mca_repository.c:1-225 —    #
# components discoverable/loadable by type; round-2 VERDICT missing #5) #
# --------------------------------------------------------------------- #
def test_mca_builtin_tables():
    # the framework packages register their built-ins at import (the
    # analog of static component tables linked into the binary)
    import parsec_tpu.profiling.pins    # noqa: F401
    import parsec_tpu.runtime.termdet   # noqa: F401
    import parsec_tpu.sched             # noqa: F401
    from parsec_tpu.utils import mca

    assert "lfq" in mca.components("sched")
    assert "fourcounter" in mca.components("termdet")
    assert "task_profiler" in mca.components("pins")
    assert {"sched", "termdet", "pins"} <= set(mca.frameworks())


def test_mca_dotted_path_loads_out_of_tree_component(tmp_path, monkeypatch):
    """--mca sched mypkg.mod:Class plugs an external scheduler in with
    no code changes (the reference's dynamic component load)."""
    import sys

    mod = tmp_path / "xsched_mod.py"
    mod.write_text(
        "from parsec_tpu.sched.modules import GDScheduler\n"
        "class FancySched(GDScheduler):\n"
        "    name = 'fancy'\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    from parsec_tpu.sched import sched_new
    from parsec_tpu.utils import mca

    s = sched_new("xsched_mod:FancySched")
    assert type(s).__name__ == "FancySched"
    # cached in the framework table after the first open
    assert mca.open_component("sched", "xsched_mod:FancySched") is type(s)
    sys.modules.pop("xsched_mod", None)


def test_mca_unknown_component_is_none_and_sched_falls_back():
    from parsec_tpu.sched import sched_new
    from parsec_tpu.utils import mca

    assert mca.open_component("sched", "no_such_sched") is None
    s = sched_new("no_such_sched")     # logs help, falls back to lfq
    assert type(s).name == "lfq"


def test_mca_scheduler_end_to_end(tmp_path, monkeypatch):
    """A dynamically loaded scheduler actually drives a context."""
    import numpy as np

    mod = tmp_path / "xsched_e2e.py"
    mod.write_text(
        "from parsec_tpu.sched.modules import GDScheduler\n"
        "class E2ESched(GDScheduler):\n"
        "    name = 'e2e'\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    import parsec_tpu
    from parsec_tpu.collections import LocalArrayCollection
    from parsec_tpu.dsl import ptg

    ctx = parsec_tpu.Context(nb_cores=1, scheduler="xsched_e2e:E2ESched",
                             enable_tpu=False)
    try:
        arr = np.zeros((4, 1))
        coll = LocalArrayCollection(arr, 4)
        tp = ptg.compile_jdf("""
descA [ type="collection" ]
N [ type="int" ]

T(k)
k = 0 .. N-1
: descA( k )
RW A <- descA( k )
     -> descA( k )
BODY
{
    A[0] = k + 1.0
}
END
""", name="mcae2e").new(descA=coll, N=4)
        ctx.add_taskpool(tp)
        ctx.wait()
        np.testing.assert_allclose(arr[:, 0], [1, 2, 3, 4])
    finally:
        ctx.fini()


def test_every_registered_param_has_a_reader():
    """A registered name chooses something: each name a ``params.reg_*``
    call registers appears as a quoted literal, outside its own
    registration, somewhere in the program, its tools, examples or
    benchmark.  A name nothing reads is documentation of a choice the
    code does not offer."""
    import ast

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(repo, f)
             for f in ("chip_smoke.py", "__graft_entry__.py")]
    for top in ("parsec_tpu", "tools", "examples", "perfbench"):
        for d, _dirs, names in os.walk(os.path.join(repo, top)):
            files += [os.path.join(d, n) for n in names if n.endswith(".py")]
    registered, literals = set(), set()
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read(), path)
        own = set()     # the literal nodes that ARE a registration
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr.startswith("reg_") \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "params" and node.args \
                    and isinstance(node.args[0], ast.Constant):
                registered.add(node.args[0].value)
                own.add(id(node.args[0]))
        literals |= {node.value for node in ast.walk(tree)
                     if isinstance(node, ast.Constant)
                     and isinstance(node.value, str) and id(node) not in own}
    assert len(registered) > 50, registered     # the parse found the registry
    unread = sorted(registered - literals)
    assert not unread, f"registered, read by nothing: {unread}"
