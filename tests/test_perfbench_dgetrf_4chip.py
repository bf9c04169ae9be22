"""The benchmark's side of LU on a 1 x 4 grid (cell
``dgetrf-4chip.n40960-nb1024``): what its files state, the new per-layer
readers on hand-made observations, and the cell through the harness's
CPU rehearsal on four virtual devices.  Counts only.
"""
import json
import os
import subprocess
import sys

import pytest

from parsec_tpu.obs import phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402

CELL = "dgetrf-4chip.n40960-nb1024"
NEW = ("peer_pulls_per_call", "untraced_peer_pull_s")
LU_METRICS = ("panel_device_s", "update_device_s", "laswp_device_s",
              "panel_roofline", "update_roofline", "laswp_roofline",
              "panel_strip_device_s", "panel_pass_device_s",
              "update_kernel_device_s", "scratch_stage_in_gb")
FOUR_CHIP_METRICS = ("placed_by_advice_per_call", "placed_by_owner_per_call",
                     "placed_by_load_per_call", "device_task_imbalance_pct",
                     "busiest_chip_busy_s")


def test_the_cell_is_the_issues():
    cell = spec.Cell(spec.load_benchmark(), CELL)
    assert (cell.chips, cell.op_name, cell.config_name, cell.traffic_name) \
        == (4, "dgetrf_1d_1xq", "dgetrf-f32-4chip", "n40960-nb1024")
    assert cell.op["entry"] == "parsec_tpu.ops:dgetrf_1d"
    assert "warm_up" not in cell.op and cell.args == {}
    assert {k: cell.sizes[k] for k in ("N", "NB", "NT")} \
        == {"N": 40960, "NB": 1024, "NT": 40}
    assert cell.kernel_counts() == {"PANEL": 40, "UPDATE": 780, "LASWP": 39}
    assert cell.flops() == pytest.approx(45.8e12, rel=2e-3)
    cfg = cell.config
    assert cfg["mca"] == {} and cfg["architecture"] is None
    assert cfg["matmul_precision"] == "highest" and cfg["reduced"] == ["N"]
    assert set(cfg["guarantees"]) >= {"placement", "layout", "pivoting",
                                      "permutation", "precision",
                                      "no_downgrade", "broadcast",
                                      "residence"}
    assert set(cfg["assumed"]) >= {"storage_dtype", "NB", "grid",
                                   "broadcast", "deployment"}
    assert 0 < cfg["check"]["limit"] <= 3e-4


def _data(kind, name):
    with open(os.path.join(ROOT, "perfbench", kind, name + ".json")) as f:
        return json.load(f)


def test_the_operation_is_dgetrf_1ds_but_for_its_reference():
    """The call, the collection, the classes and the flop model of cells
    6 and 9, unchanged; the reference is theirs behind a gate."""
    mine, theirs = _data("operations", "dgetrf_1d_1xq"), \
        _data("operations", "dgetrf_1d")
    assert mine.pop("reference") == "lu_1xq"
    assert theirs.pop("reference") == "lu"
    mine.pop("note"), theirs.pop("note")
    assert mine == theirs


@pytest.mark.parametrize("cls", ["PANEL", "UPDATE", "LASWP"])
def test_the_kernel_file_counts_what_dgetrf_1ds_counts(cls):
    mine, theirs = _data("kernels", "dgetrf_1d_1xq." + cls), \
        _data("kernels", "dgetrf_1d." + cls)
    assert {k: mine[k] for k in ("class", "count", "flops", "bytes")} \
        == {k: theirs[k] for k in ("class", "count", "flops", "bytes")}


def _load_lu_1xq(name):
    import importlib.util
    path = os.path.join(ROOT, "perfbench", "reference", "lu_1xq.py")
    s = importlib.util.spec_from_file_location(
        "perfbench.reference." + name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_the_reference_is_lu_pys_where_the_program_lays_out_1xq():
    from perfbench.reference import lu
    cell = spec.Cell(spec.load_benchmark(), CELL)
    ref = cell.reference()
    assert ref.__name__ == "perfbench.reference.lu_1xq"
    for name in ("make_input", "expected", "residual", "plain_factor",
                 "L_MAX"):
        assert getattr(ref, name) is getattr(lu, name)


def test_the_reference_refuses_a_program_without_the_layout(monkeypatch):
    """The parent of PR 50: ``ops.dgetrf_1d`` places columns by load."""
    import importlib
    # (``parsec_tpu.ops.dgetrf_1d`` the attribute is the entry point)
    program = importlib.import_module("parsec_tpu.ops.dgetrf_1d")
    monkeypatch.delattr(program, "layout_1xq")
    with pytest.raises(spec.SpecError, match="no 1 x Q layout"):
        _load_lu_1xq("lu_1xq_without")


@pytest.mark.parametrize("name", NEW + LU_METRICS + FOUR_CHIP_METRICS)
def test_the_metric_is_listed_for_the_cell(name):
    bench = spec.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == name)
    assert entry["moves"] == "factor_s"
    if name in NEW:
        assert entry["workloads"] == [CELL]
        assert (entry["better"], entry["layer"], entry["source"]) \
            == ("lower", "data movement", "program_counter")
    else:
        assert entry["workloads"][-1] == CELL
    assert callable(spec.metric_reader(name).read)


def test_peer_pulls_per_call_reads_the_counter():
    read = spec.metric_reader("peer_pulls_per_call").read
    assert read({"counters": {"peer_pulls": 1512}, "n_counted": 2}) == 756.0
    assert read({"counters": {"peer_pulls": 0}, "n_counted": 2}) == 0
    # the parent: no such counter; a window that counted no call
    assert read({"counters": {"tasks": 859}, "n_counted": 1}) is None
    assert read({"counters": {"peer_pulls": 7}, "n_counted": 0}) is None


def _record(root_s, traced, pull_s, devices=4, peer=True):
    entry = {b: {"wall_ns": 0, "count": 0} for b in phases.BRACKETS}
    if peer:
        entry["peer"] = {"peer_pulls": 10, "stage_in_peer_bytes": 1 << 20,
                         "peer_pull_ns": int(pull_s * 1e9)}
    return {"op": "dgetrf_1d", "id": 1, "t0_ns": 0,
            "t1_ns": int(root_s * 1e9), "traced": traced,
            "manager": {b: {"wall_ns": 0, "count": 0}
                        for b in phases.BRACKETS},
            "by_device": [dict(entry, device=f"tpu:{i}")
                          for i in range(devices)]}


@pytest.fixture
def records():
    phases.clear_completed()
    yield phases._completed
    phases.clear_completed()


def test_untraced_peer_pull_s_sums_the_managers_over_untraced_calls(records):
    read = spec.metric_reader("untraced_peer_pull_s").read
    walls = [2.0, 2.5, 2.5, 2.0]
    obs = {"walls": walls, "n_traced": 2, "n_counted": 4}
    records.extend([_record(9.0, False, 9.0),            # set-up's call
                    _record(1.9, False, 0.10), _record(2.4, True, 0.50),
                    _record(2.4, True, 0.50), _record(1.9, False, 0.20)])
    # four managers: (4 x 0.10 + 4 x 0.20) / 2 untraced calls
    assert read(obs) == pytest.approx(0.6)
    # nothing rather than zero: no record, no untraced call, the parent's
    # records (no ``peer`` block)
    assert read(dict(obs, walls=[])) is None
    records.clear()
    records.extend([_record(2.4, True, 0.5), _record(2.4, True, 0.5)])
    assert read(dict(obs, walls=[2.5, 2.5])) is None
    records.clear()
    records.extend(_record(1.9, False, 0.1, peer=False) for _ in walls)
    assert read(dict(obs, walls=[2.0] * 4)) is None


def test_panel_chain_gap_s_on_two_chips():
    """Two traced calls of NT = 3 on two chips (ms): the panels go
    round the chips; the gap is start of the next less end of the last,
    inside one call; the refill between the calls is no gap; an UPDATE
    program and a stacked one are not panels."""
    ms = 1e6
    reader = spec.metric_reader("panel_chain_gap_s")
    chips = {
        0: {"ops": [], "modules": [
            ("jit_PANEL(11)", 0 * ms, 10 * ms),
            ("jit_UPDATE_x2(12)", 12 * ms, 5 * ms),
            ("jit_PANEL(11)", 41 * ms, 6 * ms),          # PANEL(2)
            ("jit_PANEL(11)", 1000 * ms, 10 * ms),       # second call
            ("jit_PANEL(11)", 1045 * ms, 6 * ms)]},
        1: {"ops": [], "modules": [
            ("jit_UPDATE(13)", 13 * ms, 4 * ms),
            ("jit_PANEL(21)", 20 * ms, 8 * ms),          # PANEL(1)
            ("jit_LASWP_x2(14)", 50 * ms, 1 * ms),
            ("jit_PANEL(21)", 1022 * ms, 8 * ms)]}}
    spans = [("traced", -5 * ms, 1100 * ms),
             ("tile_input", -4 * ms, -1 * ms),
             ("entry_call", -1 * ms, 60 * ms),
             ("tile_input", 60 * ms, 999 * ms),
             ("entry_call", 999 * ms, 1090 * ms)]
    events = {"chips": chips, "spans": spans}
    # call 1: (20 - 10) + (41 - 28); call 2: (1022 - 1010) + (1045 - 1030)
    assert reader.chain_gaps(events) == pytest.approx([0.023, 0.027])
    assert reader.read({"trace": {"events": events}}) == pytest.approx(0.025)
    # no call span: one call, the refill read as a gap (a trace by hand)
    assert reader.chain_gaps({"chips": chips, "spans": []}) \
        == pytest.approx([0.023 + 0.953 + 0.027])
    # the harness's reduction keeps no event: nothing, never zero
    assert reader.read({"trace": {"modules_s": {"jit_PANEL(11)": 0.03}}}) \
        is None
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": {"events": {"chips": {}, "spans": []}}}) \
        is None


def test_the_cell_rehearses_on_four_devices_with_its_counts():
    """NT = 8 on four virtual devices through ``perfbench/run.py``: the
    layout's and the pulls' counts a call in the ``counters`` line, the
    two counter metrics in a traced rehearsal's metrics."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PARSEC_MCA_")}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 23), "--seconds", "1",
         "--trace", "1", "--rehearse", "256,32"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert "43 tasks {'PANEL': 8, 'UPDATE': 28, 'LASWP': 7}" in lines[0]
    said = next(ln for ln in lines if "never a result" in ln)
    result = json.loads(said.split("): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    n = result["attempted"]
    window = next(ln for ln in lines if ln.startswith("REHEARSAL window:"))
    for counter, a_call in (("tasks", 43), ("placed_by_advice", 8),
                            ("placed_by_owner", 35), ("placed_by_load", 0),
                            ("peer_pulls", 52),
                            ("stage_in_peer_bytes", 18 * 256 * 32 * 4
                             + 3 * 4 * 256 * 4)):
        assert f"'{counter}': {a_call * n}," in window, counter
    metrics = result["metrics"]
    assert metrics["peer_pulls_per_call"]["value"] == 52
    assert metrics["placed_by_advice_per_call"]["value"] == 8
    assert metrics["placed_by_load_per_call"]["value"] == 0
    assert metrics["untraced_peer_pull_s"]["value"] == "not measured"
    assert "panel_chain_gap_s" not in metrics
