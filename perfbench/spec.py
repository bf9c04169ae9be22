"""Everything the harness knows, it reads from data files found by name.

``BENCHMARK.json`` names cells, configurations and metrics; a cell names
its configuration and its traffic; a configuration names its operation;
an operation names its entry point, its operands, its reference and
its kernel classes.  Adding any of them is adding files and entries:
nothing here or in ``run.py`` lists a name.
"""
import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: what an operation file says of an operand: read only, or written in
#: place (the timer ends when every tile of every ``inout`` one is ready)
MODES = ("in", "inout")
ONE_MATRIX_IN_PLACE = [{"name": "A", "mode": "inout"}]


class SpecError(Exception):
    """A data file or BENCHMARK.json entry the harness cannot use."""


def check_name(name, what):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name is 1 to 64 of a-z A-Z "
                        f"0-9 _ . - and starts with a letter, digit or _")
    return name


def check_unit(unit, what):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not 1 to 16 of a-z A-Z "
                        f"0-9 _ / % . -")
    return unit


def _read_json(path, what):
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def data_file(kind, name):
    """``perfbench/<kind>/<name>.json`` of a checked name."""
    check_name(name, kind)
    return _read_json(os.path.join(HERE, kind, name + ".json"),
                      f"{kind} {name!r}")


def formula(expr, env):
    """A count, flop or byte formula of a data file: arithmetic over the
    names the traffic and the operation's grid define, nothing else."""
    return eval(expr, {"__builtins__": {}}, dict(env))  # noqa: S307


def _operands_of(op, what):
    """[(name, mode)] in the order the entry point takes them after the
    context; an operation file without ``operands`` is one matrix
    factored in place."""
    operands = op.get("operands", ONE_MATRIX_IN_PLACE)
    if not isinstance(operands, list) or not operands:
        raise SpecError(f"{what}: 'operands' is a list of at least one "
                        f"{{'name': ..., 'mode': ...}}")
    out = []
    for o in operands:
        if not isinstance(o, dict) or set(o) != {"name", "mode"}:
            raise SpecError(f"{what}: operand {o!r} has not just a 'name' "
                            f"and a 'mode'")
        check_name(o["name"], f"{what}: operand")
        if o["mode"] not in MODES:
            raise SpecError(f"{what}: operand {o['name']!r} has mode "
                            f"{o['mode']!r}, none of {MODES}")
        out.append((o["name"], o["mode"]))
    if len({n for n, _ in out}) != len(out):
        raise SpecError(f"{what}: two operands share a name: {out}")
    if not any(mode == "inout" for _, mode in out):
        raise SpecError(f"{what}: no operand is 'inout', so the call "
                        f"writes nothing a check could read")
    return out


def _args_of(op, what):
    """Keyword arguments of the entry point: scalars only."""
    args = op.get("args", {})
    if not isinstance(args, dict):
        raise SpecError(f"{what}: 'args' is an object of keyword arguments")
    for k, v in args.items():
        if not k.isidentifier() \
                or not isinstance(v, (bool, int, float, str)):
            raise SpecError(f"{what}: argument {k!r} = {v!r} is not a "
                            f"scalar under a keyword")
    return dict(args)


def _warm_up_of(op, names, what):
    """Set-up calls on small grids of the same tile, for an operation
    whose stacked programs a call at the cell's size meets only now and
    then: {"rounds": n, "grids": [{operand: [rows, columns], ...}, ...]},
    rows and columns counted in tiles, each a number or a formula of the
    cell's sizes; every grid is called once a round.  Absent: none."""
    plan = op.get("warm_up")
    if plan is None:
        return None
    ok = isinstance(plan, dict) and set(plan) == {"rounds", "grids"} \
        and type(plan["rounds"]) is int and plan["rounds"] >= 1 \
        and isinstance(plan["grids"], list) and plan["grids"] \
        and all(isinstance(g, dict) and set(g) == set(names)
                and all(isinstance(rc, list) and len(rc) == 2
                        and all(type(e) in (int, str) for e in rc)
                        for rc in g.values())
                for g in plan["grids"])
    if not ok:
        raise SpecError(f"{what}: 'warm_up' is {{'rounds': a count from 1, "
                        f"'grids': [{{operand: [rows, columns] in tiles "
                        f"for each of {sorted(names)}}}, ...]}}")
    return plan


class Cell:
    """One entry of ``workloads`` with every file it leads to."""

    def __init__(self, bench, name):
        check_name(name, "workload")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SpecError(
                f"workload {name!r} is not in BENCHMARK.json (it has "
                f"{[w['name'] for w in bench['workloads']]})")
        self.name = name
        self.chips = int(entry["chips"])
        cfg_entry = next((c for c in bench["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"workload {name!r}: configuration "
                            f"{entry['config']!r} is not in BENCHMARK.json")
        self.config_name = check_name(cfg_entry["name"], "config")
        self.config = _read_json(os.path.join(ROOT, cfg_entry["file"]),
                                 f"config {self.config_name!r}")
        if int(self.config["chips"]) != self.chips:
            raise SpecError(f"workload {name!r} asks for {self.chips} "
                            f"chip(s), its configuration for "
                            f"{self.config['chips']}")
        self.traffic_name = check_name(entry["traffic"], "traffic")
        self.traffic = data_file("traffic", self.traffic_name)
        if (self.traffic["loop"], self.traffic["callers"]) != ("closed", 1) \
                or self.traffic["matrix"] not in ("refilled", "fresh"):
            raise SpecError(f"traffic {self.traffic_name!r}: the generator "
                            f"drives a closed loop of one caller over a "
                            f"'refilled' or 'fresh' matrix")
        self.op_name = check_name(self.config["operation"], "operation")
        self.op = data_file("operations", self.op_name)
        self.operands = _operands_of(self.op, f"operation {self.op_name!r}")
        self.args = _args_of(self.op, f"operation {self.op_name!r}")
        self.warm_up = _warm_up_of(self.op, [n for n, _ in self.operands],
                                   f"operation {self.op_name!r}")
        self.sizes = {}
        self.resize(**{k: v for k, v in self.traffic.items()
                       if isinstance(v, int) and not isinstance(v, bool)})
        self.kernels = [data_file("kernels", f"{self.op_name}.{k}")
                        for k in self.op["kernels"]]
        self.metrics = _metrics_of(bench, name)

    def resize(self, **sizes):
        """Set problem sizes (the traffic file's; a rehearsal's tiny
        ones) and what the operation's grid derives from them."""
        self.sizes.update(sizes)
        for k, expr in self.op.get("grid", {}).items():
            self.sizes[k] = formula(expr, self.sizes)

    def warm_up_grids(self):
        """[{operand: (rows, columns) in tiles}] of one round of the
        operation's small set-up calls; empty where it asks none."""
        if self.warm_up is None:
            return []
        grids = []
        for g in self.warm_up["grids"]:
            grid = {name: tuple(int(formula(str(e), self.sizes)) for e in rc)
                    for name, rc in g.items()}
            if min(min(rc) for rc in grid.values()) < 1:
                raise SpecError(f"operation {self.op_name!r}: warm_up grid "
                                f"{g} has no tile at sizes {self.sizes}")
            grids.append(grid)
        return grids

    def kernel_counts(self):
        return {k["class"]: int(formula(k["count"], self.sizes))
                for k in self.kernels}

    def n_tasks(self):
        return sum(self.kernel_counts().values())

    def flops(self):
        return float(formula(self.op["flops"], self.sizes))

    def reference(self):
        ref = check_name(self.op["reference"], "reference")
        return importlib.import_module(f"perfbench.reference.{ref}")

    def entry(self):
        mod, _, attr = self.op["entry"].partition(":")
        return getattr(importlib.import_module(mod), attr)

    def collection(self):
        mod, _, attr = self.op["collection"].partition(":")
        return getattr(importlib.import_module(mod), attr)


def _metrics_of(bench, cell_name):
    """The cell's metrics: {"end_to_end": [...], "per_layer": [...]},
    each entry checked for its name, unit and source."""
    out = {}
    for group in ("end_to_end", "per_layer"):
        out[group] = []
        for m in bench[group]:
            check_name(m["name"], f"{group} metric")
            check_unit(m["unit"], f"metric {m['name']!r}")
            if m["source"] not in SOURCES:
                raise SpecError(f"metric {m['name']!r}: source "
                                f"{m['source']!r} is none of {SOURCES}")
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"metric {m['name']!r}: better "
                                f"{m['better']!r}")
            if "workloads" in m and cell_name not in m["workloads"]:
                continue
            out[group].append(m)
    return out


def load_benchmark():
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"),
                      "BENCHMARK.json")


def metric_reader(name):
    """``perfbench/metrics/<name>.py``: a module with ``read(obs)`` that
    returns the value, or None where it finds nothing to read."""
    check_name(name, "metric")
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"metric {name!r}: no reader "
                        f"{os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_of(device_kind):
    table = _read_json(os.path.join(HERE, "peaks.json"), "peaks table")
    if device_kind not in table["devices"]:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"perfbench/peaks.json (it has "
                        f"{sorted(table['devices'])}); add its published "
                        f"peaks with their source, there is no default")
    return table["devices"][device_kind]
