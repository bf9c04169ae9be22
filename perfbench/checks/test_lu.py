"""What ``test_perfbench.py`` keeps for the other operations, for
``dgetrf_1d`` and what PR 30 added beside it; run by hand, outside
tier-1 (tier-1 has the same halves in ``tests/test_dgetrf_1d.py``):

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/checks/test_lu.py -q -p no:cacheprovider
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import class_roofline, roofline, spec  # noqa: E402
from perfbench.reference import lu  # noqa: E402

CELL = "dgetrf.n16384-nb512"


def test_task_counts_and_flops_are_the_dags():
    c = spec.Cell(spec.load_benchmark(), CELL)
    assert c.kernel_counts() == {"PANEL": 32, "UPDATE": 496, "LASWP": 31}
    assert c.n_tasks() == 559
    assert c.flops() == pytest.approx(2 * 16384 ** 3 / 3)
    # the classes' useful flops are means over the DAG and add up to
    # the operation's: sum_k (m_k NB^2 - NB^3 / 3) for the panels, and
    # (NT-1-k) (NB^3 + 2 (m_k - NB) NB^2) for the updates, m_k = N - k NB
    nb, nt, n = 512, 32, 16384
    by_class = {k["class"]: k for k in c.kernels}
    total = {cls: c.kernel_counts()[cls] * spec.formula(k["flops"], c.sizes)
             for cls, k in by_class.items()}
    assert total["PANEL"] == pytest.approx(sum(
        (n - k * nb) * nb ** 2 - nb ** 3 / 3 for k in range(nt)))
    assert total["UPDATE"] == pytest.approx(sum(
        (nt - 1 - k) * (nb ** 3 + 2 * (n - (k + 1) * nb) * nb ** 2)
        for k in range(nt)))
    assert sum(total.values()) == pytest.approx(c.flops(), rel=1e-12)
    moved = sum((nt - 1 - k) * 3 * 4 * (n - k * nb) * nb for k in range(nt))
    assert 496 * spec.formula(by_class["UPDATE"]["bytes"], c.sizes) \
        == pytest.approx(moved)


def test_control_is_not_correct():
    """The plain reference in the program's place passes at the
    configuration's precision and misses its limit one precision below
    ('high') and two ('default')."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "dgetrf-f32-1chip.json")) as f:
        limit = json.load(f)["check"]["limit"]
    for seed in (3, 2 ** 31 + 11, 77):
        M = lu.make_input(2048, seed)    # at 512 'high' reads 2.0e-4
        exp = lu.expected(M, seed)
        sound = lu.residual(lu.plain_factor(M, 128, "highest"), exp)
        high = lu.residual(lu.plain_factor(M, 128, "high"), exp)
        low = lu.residual(lu.plain_factor(M, 128, "default"), exp)
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


def test_a_broken_interchange_is_not_correct(monkeypatch):
    """The rest of a run with the chip gate skipped and the left block
    columns left in the row order of their own panel."""
    from parsec_tpu import ops
    assert rehearse(monkeypatch, 2 ** 31 + 5)["correct"] is True
    monkeypatch.setattr(ops, "getrf_1d_laswp", lambda a, p, f: a)
    result = rehearse(monkeypatch, 2 ** 31 + 5)
    assert result["correct"] is False and result["failed"] >= 1


def test_class_roofline_is_count_times_least_time_over_class_seconds(
        monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    monkeypatch.setattr(spec, "peaks_of", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell = spec.Cell(spec.load_benchmark(), CELL)
    obs = {"chips": 1, "n_traced": 2, "trace": {"modules_s": {
        "jit_UPDATE_x16(123)": 0.5, "jit_UPDATE(9)": 0.1,
        "jit_PANEL(1)": 0.8}}}
    update = next(k for k in cell.kernels if k["class"] == "UPDATE")
    least, _ = roofline.least_time([update], cell.sizes, {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert class_roofline.read(obs, "UPDATE") == pytest.approx(
        100 * least / 0.3)
    assert class_roofline.read(obs, "LASWP") is None    # no such program
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert class_roofline.read(obs, "UPDATE") is None   # no cell named
