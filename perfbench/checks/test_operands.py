"""What ``test_perfbench.py`` keeps for the one-matrix operations, for an
operation of several operands (``operations/dgemm.json``) and for the
operand list itself; run by hand, outside tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks/test_operands.py -q -p no:cacheprovider
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.reference import gemm  # noqa: E402

RUN = [sys.executable, os.path.join(ROOT, "perfbench", "run.py")]
CELL = "dgemm.n24576-nb2048"
BENCH = spec.load_benchmark()

with open(os.path.join(ROOT, "perfbench", "configs",
                       "dgemm-f32-1chip.json")) as f:
    LIMIT = json.load(f)["check"]["limit"]


# ---- the operand list is data ------------------------------------------
@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_builds(name):
    cell = spec.Cell(BENCH, name)
    assert cell.operands and cell.n_tasks() > 0
    assert [m for _, m in cell.operands].count("inout") >= 1
    if "operands" not in cell.op:       # one matrix factored in place
        assert cell.operands == [("A", "inout")] and cell.args == {}


def test_the_gemm_cell_is_what_its_files_say():
    cell = spec.Cell(BENCH, CELL)
    assert cell.operands == [("A", "in"), ("B", "in"), ("C", "inout")]
    assert cell.args == {"alpha": gemm.ALPHA, "beta": gemm.BETA}
    assert cell.sizes["NT"] == 12 and cell.n_tasks() == 12 ** 3
    assert cell.flops() == pytest.approx(2 * 24576 ** 3)
    # three matrices staged in a call, bytes
    assert 3 * 4 * cell.sizes["N"] ** 2 == pytest.approx(7.25e9, rel=1e-3)


@pytest.mark.parametrize("operands", [
    [],
    "A",
    [{"name": "A", "mode": "in"}],                                  # no inout
    [{"name": "A", "mode": "in"}, {"name": "B", "mode": "in"}],
    [{"name": "a b", "mode": "inout"}],                             # bad name
    [{"name": "", "mode": "inout"}],
    [{"name": "A", "mode": "out"}],                                 # bad mode
    [{"name": "A", "mode": "inout"}, {"name": "A", "mode": "in"}],  # twice
    [{"name": "A", "mode": "inout", "dtype": "int32"}],             # a key more
    [{"name": "A"}],
])
def test_refuses_a_bad_operand_list(operands):
    with pytest.raises(spec.SpecError):
        spec._operands_of({"operands": operands}, "test")


@pytest.mark.parametrize("args", [
    [1.0], {"alpha": [1.0]}, {"alpha": None}, {"not a keyword": 1.0}])
def test_refuses_arguments_that_are_not_scalars_by_keyword(args):
    with pytest.raises(spec.SpecError):
        spec._args_of({"args": args}, "test")


def test_absent_means_one_matrix_in_place_and_no_argument():
    assert spec._operands_of({}, "test") == [("A", "inout")]
    assert spec._args_of({}, "test") == {}
    assert spec._warm_up_of({}, ["A"], "test") is None


GRID = {"A": [1, "NT"], "B": ["NT", 2], "C": [1, 2]}


@pytest.mark.parametrize("plan", [
    [], {"grids": [GRID]}, {"rounds": 1}, {"rounds": 1, "grids": []},
    {"rounds": 0, "grids": [GRID]}, {"rounds": True, "grids": [GRID]},
    {"rounds": 1, "grids": [GRID], "N": 4096},
    {"rounds": 1, "grids": [{"A": [1, 1], "C": [1, 1]}]},       # B missing
    {"rounds": 1, "grids": [dict(GRID, D=[1, 1])]},             # no such
    {"rounds": 1, "grids": [dict(GRID, C=[1, 2, 3])]},
    {"rounds": 1, "grids": [dict(GRID, C=[1.5, 2])]},
])
def test_refuses_a_bad_warm_up_plan(plan):
    with pytest.raises(spec.SpecError):
        spec._warm_up_of({"warm_up": plan}, ["A", "B", "C"], "test")


def test_small_set_up_grids_are_the_operation_files():
    """Only an operation file that asks for them gets set-up calls on
    small grids: tiles counted from the cell's own sizes, every grid
    once a round of the plan's; the products conform and a k-level holds 16, 8, 4 and
    2 tasks, the first over all of the cell's k."""
    cell = spec.Cell(BENCH, CELL)
    grids = cell.warm_up_grids()
    assert len(grids) == len(cell.warm_up["grids"])
    assert cell.warm_up["rounds"] >= 1
    for g in grids:
        assert g["A"][1] == g["B"][0]
        assert g["C"] == (g["A"][0], g["B"][1])
    assert [g["C"][0] * g["C"][1] for g in grids] == [16, 8, 4, 2]
    assert grids[0]["A"][1] == cell.sizes["NT"] == 12
    cell.resize(N=128, NB=128)      # NT = 1: a grid of 4 x 1 tiles stands
    assert cell.warm_up_grids()[0]["A"] == (4, 1)
    cell.op = dict(cell.op, warm_up={"rounds": 1, "grids": [
        {"A": ["NT - 1", 1], "B": [1, 1], "C": [1, 1]}]})
    cell.warm_up = cell.op["warm_up"]
    with pytest.raises(spec.SpecError):
        cell.warm_up_grids()
    for w in BENCH["workloads"]:
        other = spec.Cell(BENCH, w["name"])
        if "warm_up" not in other.op:
            assert other.warm_up_grids() == []


# ---- the harness through its CPU rehearsal, as a process ---------------
@pytest.mark.parametrize("workload,tasks", [
    (CELL, "64 tasks {'GEMM': 64}"),
    ("dpotrf.n16384-nb512", "20 tasks {'POTRF': 4, 'TRSM': 6, 'SYRK': 6, "
                            "'GEMM': 4}"),
])
def test_rehearsal_as_a_process(workload, tasks):
    p = subprocess.run(
        RUN + ["--workload", workload, "--seed", str(2 ** 31 + 17),
               "--seconds", "2", "--rehearse", "512,128"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert all(ln.startswith("REHEARSAL ") for ln in lines)
    assert tasks in lines[0]
    said = next(ln for ln in lines if "never a result" in ln)
    result = json.loads(said.split("): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert [ln for ln in p.stderr.splitlines()
            if ln.startswith("compared ")][-2:] == [
        f"compared {k}: {c['value']} limit {c['limit']:g}"
        for k, c in result["compared"].items()]
    # never the contract's last line
    assert lines[-1] == "REHEARSAL no result line: this was a CPU dry run"


# ---- the comparison that decides `correct`, and its control -------------
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 77])
def test_control_is_not_correct(seed):
    """The plain reference in the program's place passes at the
    configuration's precision and misses its limit one precision below
    ('high': 16 significant bits) and two ('default': 8)."""
    inputs = gemm.make_input(512, seed)
    exp = gemm.expected(inputs, seed)
    sound = gemm.residual(gemm.plain_product(inputs, 128, "highest"), exp)
    high = gemm.residual(gemm.plain_product(inputs, 128, "high"), exp)
    low = gemm.residual(gemm.plain_product(inputs, 128, "default"), exp)
    print(f"seed {seed}: highest {sound:.3e} high {high:.3e} default "
          f"{low:.3e} limit {LIMIT:g}")
    assert sound <= LIMIT
    assert 3 * LIMIT < high < low


def test_the_same_seed_gives_the_same_input():
    a, b = gemm.make_input(2048, 2 ** 31 + 5), gemm.make_input(2048,
                                                               2 ** 31 + 5)
    assert sorted(a) == ["A", "B", "C"]
    for name in a:
        assert a[name].dtype == np.float32
        assert np.array_equal(a[name], b[name])
        assert -0.5 <= a[name].min() and a[name].max() < 0.5
    assert not np.array_equal(a["A"], a["B"])
    assert not np.array_equal(a["A"][:1024], a["A"][1024:])
    other = gemm.make_input(2048, 2 ** 31 + 6)
    assert not np.array_equal(a["C"], other["C"])


def rehearse(monkeypatch, seed):
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from perfbench import run
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)
    said = []
    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.5,
                                 trace=0, rehearse="512,128")
    return run.run_cell(args, said.append), said


def test_the_program_under_the_limit_and_its_tasks_are_the_kernel_files(
        monkeypatch):
    """``ops.pdgemm`` through the harness at N = 512, NB = 128 with the
    operation file's alpha and beta: under the limit at 'highest', and
    every call ran NT^3 = 64 tasks on the accelerator device (a call
    that ran another count is failed)."""
    result, said = rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is True and result["failed"] == 0
    window = next(s for s in said if s.startswith("window:"))
    tasks = int(window.split("'tasks': ")[1].split(",")[0])
    assert tasks == 64 * result["attempted"]


@pytest.mark.parametrize("fault,broken", [
    # a step that returns its state unchanged
    ("unchanged", lambda sound: lambda c, a, b, alpha=1.0, beta=1.0: c),
    # an answer altered where it is produced: beta left out at k = 0
    ("no_beta", lambda sound: lambda c, a, b, alpha=1.0, beta=1.0:
        sound(c, a, b, alpha)),
    # and alpha taken as 1 in every product
    ("no_alpha", lambda sound: lambda c, a, b, alpha=1.0, beta=1.0:
        sound(c, a, b, 1.0, beta)),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, broken):
    """The rest of a run with the chip gate skipped and the tile kernel
    broken underneath the entry point."""
    from parsec_tpu import ops
    monkeypatch.setattr(ops, "gemm", broken(ops.gemm))
    result, said = rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1
    assert all(not c["value"] <= c["limit"]
               for c in result["compared"].values()), fault
