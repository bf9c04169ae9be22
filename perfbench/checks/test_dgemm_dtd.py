"""What ``test_perfbench.py`` and ``test_operands.py`` keep for their
operations, for the DTD product on four chips (``operations/dgemm_dtd.json``,
``configs/dgemm-dtd-f32-4chip.json``): the cell is what its files say,
the plain reference misses the configuration's limit at 16 and 8
significant bits, and a broken tile kernel under the cell's entry point
comes out not correct through the harness's own check.  By hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks/test_dgemm_dtd.py -q -p no:cacheprovider

and collected into tier-1 by ``tests/test_pdgemm_dtd.py``.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.reference import gemm  # noqa: E402

CELL = "dgemm-dtd-4chip.n32768-nb2048"

with open(os.path.join(ROOT, "perfbench", "configs",
                       "dgemm-dtd-f32-4chip.json")) as f:
    LIMIT = json.load(f)["check"]["limit"]


def test_the_cell_is_what_its_files_say():
    from parsec_tpu import ops
    cell = spec.Cell(spec.load_benchmark(), CELL)
    assert cell.chips == 4 and cell.config["mca"] == {}
    assert cell.entry() is ops.pdgemm_dtd
    assert cell.operands == [("A", "in"), ("B", "in"), ("C", "inout")]
    assert cell.args == {"alpha": gemm.ALPHA, "beta": gemm.BETA}
    assert cell.warm_up is None or cell.warm_up_grids()
    assert cell.sizes["NT"] == 16 and cell.n_tasks() == 16 ** 3
    assert cell.flops() == pytest.approx(70.4e12, rel=1e-3)
    # three matrices, more than one chip holds
    assert 3 * 4 * cell.sizes["N"] ** 2 == pytest.approx(12.9e9, rel=2e-3)
    assert cell.config["reduced"] == ["N"]
    for key in ("source", "guarantees", "assumed", "check"):
        assert cell.config[key], key
    assert set(cell.config["check"]) == {"number", "limit", "reason"}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 77])
def test_control_is_not_correct(seed):
    """The plain reference in the program's place passes at the
    configuration's precision and misses its limit one precision below
    ('high': 16 significant bits) and two ('default': 8)."""
    inputs = gemm.make_input(512, seed)
    exp = gemm.expected(inputs, seed)
    sound, high, low = (
        gemm.residual(gemm.plain_product(inputs, 128, precision), exp)
        for precision in ("highest", "high", "default"))
    print(f"seed {seed}: highest {sound:.3e} high {high:.3e} default "
          f"{low:.3e} limit {LIMIT:g}")
    assert sound <= LIMIT
    assert 3 * LIMIT < high < low


def _rehearse(monkeypatch, seed):
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from perfbench import run
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)
    said = []
    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.5,
                                 trace=0, rehearse="256,32")
    return run.run_cell(args, said.append), said


def test_the_cell_through_the_harness_at_a_rehearsal_size(monkeypatch):
    result, said = _rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is True and result["failed"] == 0
    window = next(s for s in said if s.startswith("window:"))
    for counter in ("placed_by_advice", "stage_out_bytes"):
        assert f"'{counter}': " in window       # the ``counters`` line
    tasks = int(window.split("'tasks': ")[1].split(",")[0])
    assert tasks == 8 ** 3 * result["attempted"]


@pytest.mark.parametrize("fault,broken", [
    # beta left out at k = 0 (checks/broken_kernel.py --drops 4)
    ("no_beta", lambda sound: lambda c, a, b, alpha=1.0, beta=1.0:
        sound(c, a, b, alpha)),
    # a step that returns its state unchanged
    ("unchanged", lambda sound: lambda c, a, b, alpha=1.0, beta=1.0: c),
])
def test_a_broken_kernel_is_over_the_limit(monkeypatch, fault, broken):
    from parsec_tpu import ops
    monkeypatch.setattr(ops, "gemm", broken(ops.gemm))
    result, _said = _rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1
    assert all(not c["value"] <= c["limit"]
               for c in result["compared"].values()), fault
