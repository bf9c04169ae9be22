"""Info registry, show_help catalog, and C embedding bindings
(ref: parsec/class/info.h, parsec/utils/show_help.c, parsec/fortran/).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.core.info import InfoObjectArray, InfoRegistry
from parsec_tpu.utils import show_help as sh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# info registry                                                         #
# --------------------------------------------------------------------- #
def test_info_register_lookup_recycle():
    reg = InfoRegistry()
    a = reg.register("alpha")
    b = reg.register("beta")
    assert (a, b) == (0, 1)
    assert reg.register("alpha") == a  # idempotent
    assert reg.lookup("beta") == b
    assert reg.lookup("nope") == -1
    assert reg.unregister("alpha")
    assert not reg.unregister("alpha")
    assert reg.lookup("alpha") == -1
    # freed id is recycled (ref: info.c id reuse)
    assert reg.register("gamma") == a
    assert reg.nb_registered() == 2


def test_info_object_array_lazy_construct_and_teardown():
    reg = InfoRegistry()
    host = object()
    made, torn = [], []
    iid = reg.register("slot",
                       constructor=lambda obj: made.append(obj) or {"n": 1},
                       destructor=lambda item: torn.append(item))
    arr = InfoObjectArray(reg, cons_arg=host)
    item = arr.get(iid)
    assert made == [host] and item == {"n": 1}
    assert arr.get(iid) is item  # constructed once
    arr.set(iid, {"n": 2})
    assert arr.get(iid) == {"n": 2}
    arr.clear()
    assert torn == [{"n": 2}]
    with pytest.raises(KeyError):
        arr.get(99)


def test_info_recycled_id_isolated():
    """A recycled iid must not expose the old slot's item, and clear()
    runs each item's ORIGINAL destructor (review-hardened semantics)."""
    reg = InfoRegistry()
    torn = []
    a = reg.register("a", constructor=lambda _: "item_a",
                     destructor=lambda it: torn.append(("da", it)))
    arr = InfoObjectArray(reg)
    assert arr.get(a) == "item_a"
    reg.unregister("a")
    b = reg.register("b", constructor=lambda _: "item_b",
                     destructor=lambda it: torn.append(("db", it)))
    assert b == a  # recycled id
    assert arr.get(b) == "item_b"  # fresh construction, not the stale item
    arr.clear()
    assert ("da", "item_a") in torn and ("db", "item_b") in torn


def test_info_reentrant_constructor():
    """Constructors may read other slots of the same array."""
    reg = InfoRegistry()
    base = reg.register("base", constructor=lambda _: 10)
    arr = InfoObjectArray(reg)
    derived = reg.register("derived",
                           constructor=lambda _: arr.get(base) + 1)
    assert arr.get(derived) == 11


def test_taskpool_info_lifecycle(ctx):
    """Per-taskpool info items construct on first use and are destroyed
    when the taskpool completes."""
    from parsec_tpu import dtd
    from parsec_tpu.core.info import taskpool_infos

    events = []
    iid = taskpool_infos.register(
        "test::percent_done",
        constructor=lambda tp: events.append(("make", tp.name)) or [0],
        destructor=lambda item: events.append(("destroy", item[0])))
    try:
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)
        state = tp.info.get(iid)
        tp.insert_task(lambda es, task: state.__setitem__(0, 42))
        tp.wait()
        assert ("make", tp.name) in events
        assert ("destroy", 42) in events
    finally:
        taskpool_infos.unregister(iid)


# --------------------------------------------------------------------- #
# show_help                                                             #
# --------------------------------------------------------------------- #
def test_show_help_formats_and_suppresses(capsys):
    sh.reset()
    t1 = sh.show_help("help-runtime.txt", "unknown-scheduler",
                      name="zzz", available="a, b", fallback="lfq")
    assert 'zzz' in t1 and "a, b" in t1 and "lfq" in t1
    out1 = capsys.readouterr().err + capsys.readouterr().out
    t2 = sh.show_help("help-runtime.txt", "unknown-scheduler",
                      name="zzz", available="a, b", fallback="lfq")
    assert t2 == t1  # text returned again but not re-emitted
    sh.reset()


def test_show_help_unknown_topic():
    sh.reset()
    t = sh.show_help("help-runtime.txt", "no-such-topic", foo=1)
    assert "no help found" in t
    sh.reset()


def test_unknown_scheduler_falls_back():
    from parsec_tpu.sched import sched_new
    sh.reset()
    mod = sched_new("definitely-not-a-scheduler")
    assert mod.name == "lfq"
    sh.reset()


# --------------------------------------------------------------------- #
# C embedding bindings                                                  #
# --------------------------------------------------------------------- #
C_DRIVER = r"""
#include <stdio.h>
#include "parsec_tpu_c.h"

static void saxpy_body(float **tiles, int ntiles, void *user) {
    float a = *(float *)user;
    float *y = tiles[0];
    const float *x = tiles[1];
    for (int i = 0; i < 16; i++) y[i] += a * x[i];
}

int main(void) {
    ptc_context *ctx = ptc_init(2);
    if (!ctx) { fprintf(stderr, "init: %s\n", ptc_last_error()); return 1; }
    printf("version=%s\n", ptc_version());

    float ybuf[16], xbuf[16], a = 3.0f;
    for (int i = 0; i < 16; i++) { ybuf[i] = 1.0f; xbuf[i] = (float)i; }

    ptc_taskpool *tp = ptc_dtd_taskpool_new(ctx);
    if (!tp) { fprintf(stderr, "tp: %s\n", ptc_last_error()); return 1; }
    ptc_tile *y = ptc_tile_of_dense(tp, ybuf, 4, 4);
    ptc_tile *x = ptc_tile_of_dense(tp, xbuf, 4, 4);
    ptc_tile *tiles[2] = { y, x };
    int modes[2] = { PTC_INOUT, PTC_INPUT };
    for (int k = 0; k < 3; k++) {
        if (ptc_insert_task(tp, saxpy_body, &a, 2, tiles, modes) != 0) {
            fprintf(stderr, "insert: %s\n", ptc_last_error());
            return 1;
        }
    }
    if (ptc_data_flush_all(tp) != 0) return 1;
    if (ptc_taskpool_wait(tp) != 0) {
        fprintf(stderr, "wait: %s\n", ptc_last_error());
        return 1;
    }
    /* y = 1 + 3*3*i */
    for (int i = 0; i < 16; i++) {
        float want = 1.0f + 9.0f * (float)i;
        if (ybuf[i] != want) {
            fprintf(stderr, "y[%d] = %f != %f\n", i, ybuf[i], want);
            return 2;
        }
    }
    ptc_tile_free(y);
    ptc_tile_free(x);
    ptc_taskpool_free(tp);
    ptc_fini(ctx);
    printf("C-BINDING-OK\n");
    return 0;
}
"""


def test_c_embedding_end_to_end(tmp_path):
    """Compile a C program against libparsec_tpu_c and run a 3-task saxpy
    chain through the runtime from C."""
    import sysconfig
    from parsec_tpu.bindings.build import build, libpath, python_link_flags

    build()
    bdir = os.path.join(ROOT, "parsec_tpu", "bindings")
    src = tmp_path / "driver.c"
    src.write_text(C_DRIVER)
    exe = str(tmp_path / "driver")
    subprocess.run(
        ["gcc", "-O1", str(src), "-o", exe, f"-I{bdir}",
         libpath(), f"-Wl,-rpath,{bdir}"] + python_link_flags(),
        check=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["PARSEC_MCA_device_tpu_platform"] = "cpu"
    r = subprocess.run([exe], capture_output=True, text=True, timeout=180,
                       env=env)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "C-BINDING-OK" in r.stdout
    assert "version=" in r.stdout
