"""The harness's operand cases (``perfbench/checks/test_operands.py``:
operands, arguments and set-up grids as data, the rehearsal as a
process, the control and the broken timed path of the several-operand
cell) collected into tier-1, which runs ``tests/`` only.

One case is restated: an operation file without ``operands`` is one
matrix factored in place, and since ``dpotrf_mp`` such an operation may
still take scalar arguments (its two bands).  The harness refuses to
run with a ``PARSEC_MCA_`` knob in the environment, and ``conftest.py``
sets one for every other test, so the cases here run without it (as
they do by hand)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.checks.test_operands import *  # noqa: E402,F401,F403
from perfbench.checks.test_operands import BENCH  # noqa: E402


@pytest.fixture(autouse=True)
def no_knob_in_the_environment(monkeypatch):
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_builds(name):  # noqa: F811
    cell = spec.Cell(BENCH, name)
    assert cell.operands and cell.n_tasks() > 0
    assert [m for _, m in cell.operands].count("inout") >= 1
    if "operands" not in cell.op:       # one matrix factored in place
        assert cell.operands == [("A", "inout")]
    assert cell.args == cell.op.get("args", {})
