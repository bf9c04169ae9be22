"""Checks of the yardstick itself; run by hand, outside tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks -q -p no:cacheprovider

They need no chip: the trace reduction is held against a small trace
recorded on the v5e (``data/tiny.xplane.pb``, written by
``record_trace.py``), the harness is driven through its CPU rehearsal.
"""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import roofline, spec, xplane  # noqa: E402
from perfbench.reference import cholesky, qr  # noqa: E402

RUN = [sys.executable, os.path.join(ROOT, "perfbench", "run.py")]
CELL = "dpotrf.n16384-nb512"


def run_py(*argv, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(RUN + list(argv), env=e, capture_output=True,
                          text=True, timeout=600)


def no_result_line(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


# ---- the trace reduction ------------------------------------------------
def test_merge_and_clip():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert xplane.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_synthetic():
    """Two chips, a 100 ns window, overlapping ops: the union counts, an
    op outside the window does not, gaps carry the covering span."""
    trace = {
        "chips": {
            0: {"ops": [("a", 10.0, 20.0), ("b", 20.0, 20.0),
                        ("a", 60.0, 10.0), ("late", 150.0, 10.0)],
                "modules": [("jit_f", 10.0, 30.0)]},
            1: {"ops": [("a", 0.0, 50.0)], "modules": []},
        },
        "spans": [("traced", 0.0, 100.0), ("entry_call", 0.0, 45.0),
                  ("tile_input", 45.0, 100.0)],
    }
    r = xplane.reduce(trace)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_by_chip_s"][0] == pytest.approx(40e-9)   # 10-40, 60-70
    assert r["busy_by_chip_s"][1] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["ops_s"]["a"] == pytest.approx((20 + 10 + 50) / 2 * 1e-9)
    assert "late" not in r["ops_s"]
    # chip 0 idles 0-10 (entry), 40-60 (tile), 70-100 (tile); chip 1 50-100
    assert r["idle_by_label_s"]["entry_call"] == pytest.approx(5e-9)
    assert r["idle_by_label_s"]["tile_input"] == pytest.approx(50e-9)
    assert r["longest_gaps"][0] == ("tile_input", pytest.approx(50e-9))


def test_reduce_recorded_trace():
    """The recorded v5e trace: numbers read by hand from the same file
    (``python3 perfbench/xplane.py perfbench/checks/data/tiny.xplane.pb``)."""
    path = os.path.join(HERE, "data", "tiny.xplane.pb")
    want = json.load(open(os.path.join(HERE, "data", "tiny.expected.json")))
    trace = xplane.read(xplane.load(path))
    assert sorted(trace["chips"]) == want["chips"]
    r = xplane.reduce(trace)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    for name, sec in want["idle_by_label_s"].items():
        assert r["idle_by_label_s"][name] == pytest.approx(sec, rel=1e-9)
    idle = sum(r["idle_by_label_s"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert xplane.top(r["modules_s"], 1)[0][0] == want["top_module"]


# ---- the roofline arithmetic -------------------------------------------
def test_least_time_matches_the_hand_count():
    peaks = spec.peaks_of("TPU v5 lite")
    gemm = spec.data_file("kernels", "dpotrf.GEMM")
    for nb, one, bound in ((512, 5.12e-6, "bandwidth"),
                           (2048, 87.2e-6, "compute")):
        total, rows = roofline.least_time([gemm], {"NB": nb, "NT": 4}, peaks)
        assert rows[0][1] == 4 and rows[0][3] == bound
        assert rows[0][2] == pytest.approx(one, rel=2e-3)
        assert total == pytest.approx(4 * rows[0][2])


def test_task_counts_are_the_dags():
    bench = spec.load_benchmark()
    assert spec.Cell(bench, "dpotrf.n16384-nb512").n_tasks() == 5984
    assert spec.Cell(bench, "dpotrf.n32768-nb2048").n_tasks() == 816
    c = spec.Cell(bench, "dgeqrf.n8192-nb512")
    assert c.kernel_counts() == {"GEQRT": 16, "UNMQR": 120, "TSQRT": 120,
                                 "TSMQR": 1240}
    assert c.flops() == pytest.approx(4 * 8192 ** 3 / 3)


# ---- what the harness refuses ------------------------------------------
def test_refuses_a_cpu():
    p = run_py("--workload", CELL, "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and no_result_line(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_an_mca_variable():
    p = run_py("--workload", CELL, "--seed", "1", "--seconds", "1",
               env={"PARSEC_MCA_device_batch_max": "1"})
    assert p.returncode != 0 and no_result_line(p.stdout)


def test_refuses_an_unknown_cell():
    p = run_py("--workload", "no-such.cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and no_result_line(p.stdout)
    assert "not in BENCHMARK.json" in p.stderr


def test_refuses_an_unknown_device_kind():
    with pytest.raises(spec.SpecError, match="no default"):
        spec.peaks_of("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["", "a b", "a,b", "a/b", "-lead",
                                  "x" * 65, "café", "µs"])
def test_refuses_a_bad_name(name):
    with pytest.raises(spec.SpecError):
        spec.check_name(name, "test")


@pytest.mark.parametrize("unit", ["", "tokens per second", "µs",
                                  "a,b", "x" * 17])
def test_refuses_a_bad_unit(unit):
    with pytest.raises(spec.SpecError):
        spec.check_unit(unit, "test")


def test_every_name_in_benchmark_json_passes():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        for group in cell.metrics.values():
            for m in group:
                assert hasattr(spec.metric_reader(m["name"]), "read")


# ---- the comparison that decides `correct`, and its control -------------
@pytest.mark.parametrize("cfg,ref", [("dpotrf-f32-1chip", cholesky),
                                     ("dgeqrf-f32-1chip", qr)])
def test_control_is_not_correct(cfg, ref):
    """The plain reference in the program's place passes at the
    configuration's precision and misses the configuration's limit one
    precision below ('high': three bf16 passes) and two ('default')."""
    limit = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", cfg + ".json")))["check"]["limit"]
    for seed in (3, 2 ** 31 + 11, 77):
        M = ref.make_input(512, seed)
        exp = ref.expected(M, seed)
        sound = ref.residual(ref.plain_factor(M, 64, "highest"), exp)
        high = ref.residual(ref.plain_factor(M, 64, "high"), exp)
        low = ref.residual(ref.plain_factor(M, 64, "default"), exp)
        print(f"{cfg} seed {seed}: highest {sound:.3e} high {high:.3e} "
              f"default {low:.3e} limit {limit:g}")
        assert sound <= limit < high < low


def rehearse(monkeypatch, workload, seed):
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from perfbench import run
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=0.5,
                                 trace=0, rehearse="256,32")
    return run.run_cell(args, print)


@pytest.mark.parametrize("workload,kernel,broken", [
    ("dpotrf.n16384-nb512", "gemm_nt", lambda c, a, b: c),
    ("dgeqrf.n8192-nb512", "unmqr", lambda q, c: c),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, kernel,
                                            broken):
    """The rest of a run with the chip gate skipped and one tile kernel
    returning its state unchanged underneath the entry point."""
    from parsec_tpu import ops
    assert rehearse(monkeypatch, workload, 2 ** 31 + 5)["correct"] is True
    monkeypatch.setattr(ops, kernel, broken)
    result = rehearse(monkeypatch, workload, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1
