"""What ``test_perfbench.py`` and ``test_operands.py`` keep for their
operations, for the 1D stencil (``operations/stencil_1d.json``,
``configs/stencil1d-f32-1chip.json``): the cell is what its files say,
the plain reference misses the configuration's limit when every step's
result is rounded to bf16, a broken tile kernel under the cell's entry
point comes out not correct through the harness's own check, and each
reader this cell brought gives a number where its counter or program is
and nothing where it is not.  By hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks/test_stencil.py -q -p no:cacheprovider

and collected into tier-1 by ``tests/test_stencil_1d.py``.
"""
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import roofline, spec  # noqa: E402
from perfbench.reference import stencil  # noqa: E402

STENCIL_CELL = "stencil1d.n40960-nb4096-i100"

with open(os.path.join(ROOT, "perfbench", "configs",
                       "stencil1d-f32-1chip.json")) as f:
    STENCIL_LIMIT = json.load(f)["check"]["limit"]


def test_the_stencil_cell_is_what_its_files_say():
    from parsec_tpu import ops
    cell = spec.Cell(spec.load_benchmark(), STENCIL_CELL)
    assert cell.chips == 1 and cell.config["mca"] == {}
    assert cell.entry() is ops.stencil_1d
    assert cell.operands == [("A", "inout")]
    assert cell.args == {"iterations": stencil.ITERATIONS,
                         "radius": stencil.RADIUS}
    assert tuple(ops.stencil_weights(stencil.RADIUS)) == stencil.WEIGHTS
    assert cell.sizes["NT"] == 10 and cell.sizes["I"] == stencil.ITERATIONS
    assert cell.kernel_counts() == {"STENCIL": 10_000, "SNAP": 100}
    assert cell.flops() == pytest.approx(0.839e12, rel=1e-3)
    assert 4 * cell.sizes["N"] ** 2 == pytest.approx(6.71e9, rel=1e-3)
    # the bandwidth binds the tile kernel: 164 us a task at 819 GB/s
    least, rows = roofline.least_time(
        [k for k in cell.kernels if k["class"] == "STENCIL"], cell.sizes,
        {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == pytest.approx(1.64, rel=5e-3)
    assert cell.config["reduced"] == ["N"]
    for key in ("source", "guarantees", "assumed", "check"):
        assert cell.config[key], key
    assert set(cell.config["check"]) == {"number", "limit", "reason"}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 77])
def test_stencil_control_is_not_correct(seed):
    """The plain reference in the program's place passes as float32
    and misses the limit with every step's result rounded to bf16
    (8 significant bits), by the probes and by the replayed rows."""
    U0 = stencil.make_input(256, seed)
    exp = stencil.expected(U0, seed)
    sound = stencil.plain(U0).astype(np.float32)
    control = stencil.plain(U0, rounded="default").astype(np.float32)
    s, c = stencil.residual(sound, exp), stencil.residual(control, exp)
    print(f"seed {seed}: f32 {s:.3e} bf16 a step {c:.3e} limit "
          f"{STENCIL_LIMIT:g}")
    assert s <= STENCIL_LIMIT / 3
    assert stencil.probe_number(control, exp) > 3 * STENCIL_LIMIT
    assert stencil.rows_number(control, exp) > 3 * STENCIL_LIMIT


def _rehearse_stencil(monkeypatch, seed):
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from perfbench import run
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", STENCIL_CELL,
                                      "--rehearse", "64,16"])
    said = []
    args = types.SimpleNamespace(workload=STENCIL_CELL, seed=seed,
                                 seconds=0.5, trace=0, rehearse="64,16")
    return run.run_cell(args, said.append), said


def test_the_stencil_cell_through_the_harness_at_a_rehearsal_size(
        monkeypatch):
    result, said = _rehearse_stencil(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is True and result["failed"] == 0
    window = next(s for s in said if s.startswith("window:"))
    calls = result["attempted"]

    def counted(name):      # the ``counters`` line
        return int(window.split(f"'{name}': ")[1].split(",")[0])

    assert counted("tasks") == 16 * 101 * calls
    # the counts a rehearsal shows: nothing staged in for a flow a body
    # only writes, two ghost regions of 4 R NB bytes a task
    assert counted("scratch_stage_in_bytes") == 0
    assert counted("scratch_out_bytes") == 16 * 101 * 2 * 4 * 16 * calls
    # (with several accelerators a tile SNAP read elsewhere is pulled)
    assert counted("stage_in_bytes") >= 4 * 64 * 64 * calls
    assert counted("batch_downgrades") == 0


@pytest.mark.parametrize("fault,broken", [
    # the ghosts left out (checks/broken_kernel.py --drops 1): the zero
    # boundary at every tile's edges
    ("no_ghosts", lambda sound: lambda x, *rest: sound(x)),
    # a step that returns its state unchanged (--returns 0)
    ("unchanged", lambda sound: lambda x, *rest: x),
])
def test_a_broken_stencil_kernel_is_over_the_limit(monkeypatch, fault,
                                                   broken):
    from parsec_tpu import ops
    monkeypatch.setattr(ops, "stencil_tile", broken(ops.stencil_tile))
    result, _said = _rehearse_stencil(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1
    assert all(not c["value"] <= c["limit"]
               for c in result["compared"].values()), fault


def _recorded(**counters):
    return {"chips": 1, "n_counted": 4, "n_traced": 2, "counters": counters,
            "trace": {"modules_s": {"jit_STENCIL_x16(1)": 3.0,
                                    "jit_STENCIL(2)": 0.2,
                                    "jit_SNAP_x16(3)": 0.01}}}


def test_the_new_readers_on_a_recorded_result(monkeypatch):
    read = {name: spec.metric_reader(name).read
            for name in ("stencil_device_s", "stencil_roofline",
                         "scratch_stage_in_gb", "ghost_gb")}
    obs = _recorded(scratch_stage_in_bytes=0, scratch_out_bytes=8e9)
    assert read["stencil_device_s"](obs) == pytest.approx(1.6)
    assert read["scratch_stage_in_gb"](obs) == 0
    assert read["ghost_gb"](obs) == pytest.approx(2.0)
    # the parent's result: no such counter, no program of the class
    old = _recorded()
    old["trace"]["modules_s"] = {"jit_GEMM_x16(1)": 1.0}
    for name in read:
        assert read[name](old) is None, name
    # the share: count x the least time of one task over the class's
    # device seconds, the bandwidth term binding
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", STENCIL_CELL])
    monkeypatch.setattr(
        spec, "peaks_of",
        lambda kind: {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    task_s = (4 * 2 * 4096 ** 2 + 4 * 2 * 2 * 4096) / 819e9
    assert read["stencil_roofline"](obs) == pytest.approx(
        100 * 10_000 * task_s / 1.6)
