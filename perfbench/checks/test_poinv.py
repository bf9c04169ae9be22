"""What ``test_perfbench.py`` keeps for the other operations, for
``dpoinv`` and what PR 33 added beside it; run by hand, outside tier-1
(tier-1 has the same halves in ``tests/test_dpoinv.py``):

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks/test_poinv.py -q -p no:cacheprovider
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import compound, spec  # noqa: E402
from perfbench.reference import poinv  # noqa: E402

CELL = "dpoinv.n16384-nb512"


def test_task_counts_and_flops_are_the_dags():
    c = spec.Cell(spec.load_benchmark(), CELL)
    counts = c.kernel_counts()
    assert counts["POTRF"] == counts["TRTRI"] == counts["LAUUM"] == 32
    for cls in ("TRSM", "SYRK", "TRSMR", "TRSML", "TRMM", "SYRKT"):
        assert counts[cls] == 496
    assert counts["GEMM"] == counts["GEMMI"] == counts["GEMMT"] == 4960
    assert c.n_tasks() == 3 * 5984 == 17952
    assert c.flops() == 16384 ** 3
    # parts 2 and 3 are N^3 / 3 each to the flop, part 1 dpotrf's own
    # model to its leading term
    total = {k["class"]: counts[k["class"]]
             * spec.formula(k["flops"], c.sizes) for k in c.kernels}
    for part in (("TRTRI", "TRSMR", "TRSML", "GEMMI"),
                 ("LAUUM", "TRMM", "SYRKT", "GEMMT")):
        assert sum(total[cls] for cls in part) == pytest.approx(
            16384 ** 3 / 3, rel=1e-12)
    assert sum(total.values()) == pytest.approx(c.flops(), rel=1e-3)


def test_control_is_not_correct():
    """The plain reference in the program's place passes at the
    configuration's precision and misses its limit one precision below
    ('high', 16 bits of mantissa) and two ('default', 8 bits)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "dpoinv-f32-1chip.json")) as f:
        limit = json.load(f)["check"]["limit"]
    for seed in (3, 2 ** 31 + 11, 77):
        M = poinv.make_input(2048, seed)
        exp = poinv.expected(M, seed)
        sound = poinv.residual(poinv.plain_factor(M, 128, "highest"), exp)
        high = poinv.residual(poinv.plain_factor(M, 128, "high"), exp)
        low = poinv.residual(poinv.plain_factor(M, 128, "default"), exp)
        print(f"seed {seed}: highest {sound:.3e} high {high:.3e} "
              f"default {low:.3e} limit {limit:g}")
        assert sound <= limit < high < low


def rehearse(monkeypatch, seed):
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from perfbench import run
    for k in [k for k in os.environ if k.startswith("PARSEC_MCA_")]:
        monkeypatch.delenv(k)
    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.5,
                                 trace=0, rehearse="256,32")
    return run.run_cell(args, print)


@pytest.mark.parametrize("kernel,broken", [
    ("trmm_lower_trans", lambda t, c: c),
    ("trtri_lower", lambda t: t),
])
def test_a_broken_tile_kernel_is_not_correct(monkeypatch, kernel, broken):
    """The rest of a run with the chip gate skipped and one tile kernel
    of part 2 or 3 returning its state unchanged (on the chip:
    ``broken_kernel.py --workload dpoinv.n16384-nb512 --kernel
    trmm_lower_trans --returns 1``)."""
    from parsec_tpu import ops
    assert rehearse(monkeypatch, 2 ** 31 + 5)["correct"] is True
    monkeypatch.setattr(ops, kernel, broken)
    result = rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1


def test_part_readers_read_the_records_and_nothing_else(monkeypatch):
    from parsec_tpu.obs import phases
    ms = 1_000_000
    rec = {"traced": True, "t0_ns": 0, "t1_ns": 1000 * ms, "phases": {},
           "compound_gap_ns": 30 * ms,
           "parts": [{"name": "dpotrf_L", "enqueued_ns": 0,
                      "first_call_ns": 5 * ms, "completed_ns": 300 * ms},
                     {"name": "dtrtri_L", "enqueued_ns": 301 * ms,
                      "first_call_ns": 320 * ms, "completed_ns": 700 * ms}]}
    monkeypatch.setattr(phases, "completed", lambda: [rec])
    obs = {"n_traced": 1, "walls": [9.0, 1.0]}
    assert compound.gap_seconds(obs) == pytest.approx(0.030)
    assert compound.part_seconds(obs, "dtrtri_L") == pytest.approx(0.399)
    assert compound.part_seconds(obs, "dlauum_L") is None
    plain = {k: v for k, v in rec.items()
             if k not in ("parts", "compound_gap_ns")}
    monkeypatch.setattr(phases, "completed", lambda: [plain])
    assert compound.gap_seconds(obs) is None        # a call that composed
    assert compound.part_seconds(obs, "dpotrf_L") is None       # nothing
    assert compound.gap_seconds({"n_traced": 0}) is None        # untraced
