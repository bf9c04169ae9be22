"""Batch/donation-safety lint over PTG BODY code and DTD task functions.

The device layer (devices/tpu.py + devices/batching.py) silently
downgrades per class at trace time: a ``this_task`` read makes a class
permanently unbatchable, an untraceable construct fails the first
batched flush and falls the class back to per-task dispatch, aliased
same-tile arguments suppress buffer donation per dispatch.  This pass
predicts those downgrades statically from the stdlib ``ast`` of the
body source, so a spec author learns the cost before the first run.

Finding codes (BDY2xx):

- ``BDY200`` body-syntax: the body is not valid Python.
- ``BDY201`` this-task: a device body reads ``this_task`` — the class
  NEVER batches (``batch_spec`` is withheld; every instance pays the
  per-task dyld dispatch).
- ``BDY202`` untraceable: a device body uses a construct jax cannot
  trace over device arrays (``np.*`` calls, ``print``/``open``/
  ``input``, ``.item()``/``.tolist()``, or an ``if``/``while``
  statement whose test reads a flow payload) — the first batched flush
  fails to trace and PERMANENTLY downgrades the class to per-task
  dispatch (``spec.batchable = False``).
- ``BDY203`` nondeterminism: a device body reads wall-clock time or an
  unseeded random stream — stacked executions lose the bit-exact
  batched-vs-per-task guarantee of a stacked call.
- ``BDY204`` aliased-args (warn): two flows of one class read the same
  memory tile — at dispatch the same buffer sits at two argument
  slots, so buffer donation (``device_donate``) is suppressed for
  every such dispatch.
- ``BDY205`` missing-write (warn): a device body never assigns one of
  its written (RW/WRITE) flow names — the staged-out "result" is the
  unmodified input.
- ``BDY206`` unshared-programs (warn): a device body reads something
  the program cache cannot name by value (a collection, a name the
  JDF's prologue defines, an ``import`` of its own, ``eval`` / ``exec``
  / ``globals``) — the class gets no ``cache_token``
  (dsl/ptg/body_token.py) and EVERY taskpool traces, lowers and loads
  its own copies of the class's stacked programs.

Only accelerator bodies (``BODY [type=tpu]`` and friends) are checked:
CPU bodies run on the host interpreter where all of this is legal.
"""
from __future__ import annotations

import ast as pyast
import inspect
import textwrap
from typing import Callable, List, Optional, Sequence, Set, Tuple

from ..dsl.ptg.ast import JDFFile, RangeExpr, TaskClassAST
from ..dsl.ptg.body_token import body_reads
from . import Finding

#: attribute roots whose *call* in a traced body breaks tracing
_UNTRACEABLE_ROOTS = {"np", "numpy"}
#: builtins whose call in a traced body breaks tracing (side effects /
#: host-concretization)
_UNTRACEABLE_CALLS = {"print", "open", "input"}
#: method calls that force device->host concretization
_UNTRACEABLE_METHODS = {"item", "tolist"}
#: attribute roots that make a body nondeterministic across dispatches
_NONDET_ROOTS = {"random", "time", "datetime", "uuid"}


def _attr_chain(node: pyast.AST) -> List[str]:
    """``np.random.rand`` -> ["np", "random", "rand"]; [] if not a
    simple name/attribute chain."""
    parts: List[str] = []
    while isinstance(node, pyast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, pyast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return []


def _names_under(node: pyast.AST) -> Set[str]:
    return {n.id for n in pyast.walk(node) if isinstance(n, pyast.Name)}


def _check_traced_source(tree: pyast.AST, where: str, label: str,
                         flow_names: Sequence[str],
                         findings: List[Finding]) -> None:
    """The trace-safety predicates shared by PTG device bodies and DTD
    device-chore functions."""
    flow_set = set(flow_names)
    for node in pyast.walk(tree):
        if isinstance(node, pyast.Call):
            chain = _attr_chain(node.func)
            if not chain:
                continue
            root = chain[0]
            if len(chain) == 1 and root in _UNTRACEABLE_CALLS:
                findings.append(Finding(
                    "BDY202",
                    f"{label}: call to {root}() is untraceable — the "
                    f"first batched flush fails and the class "
                    f"permanently falls back to per-task dispatch",
                    where, severity="warn"))
            elif root in _UNTRACEABLE_ROOTS:
                if len(chain) > 1 and chain[1] == "random":
                    findings.append(Finding(
                        "BDY203",
                        f"{label}: {'.'.join(chain)}(...) draws from a "
                        f"process-global random stream — batched "
                        f"executions lose bit-exact reproducibility "
                        f"(use a jax PRNG key threaded as a flow)",
                        where, severity="warn"))
                else:
                    findings.append(Finding(
                        "BDY202",
                        f"{label}: {'.'.join(chain)}(...) is a numpy "
                        f"call — it cannot trace over device arrays, "
                        f"so the first batched flush fails and the "
                        f"class permanently falls back to per-task "
                        f"dispatch (use jnp.*)",
                        where, severity="warn"))
            elif root in _NONDET_ROOTS:
                findings.append(Finding(
                    "BDY203",
                    f"{label}: {'.'.join(chain)}(...) is "
                    f"nondeterministic — stacked dispatches lose the "
                    f"bit-exact batched-vs-per-task guarantee",
                    where, severity="warn"))
            elif chain[-1] in _UNTRACEABLE_METHODS:
                findings.append(Finding(
                    "BDY202",
                    f"{label}: .{chain[-1]}() concretizes a device "
                    f"array on the host — untraceable; the class "
                    f"permanently falls back to per-task dispatch",
                    where, severity="warn"))
        elif isinstance(node, (pyast.If, pyast.While)):
            tested = _names_under(node.test)
            hot = tested & flow_set
            if hot:
                findings.append(Finding(
                    "BDY202",
                    f"{label}: {'if' if isinstance(node, pyast.If) else 'while'} "
                    f"on flow payload {sorted(hot)} concretizes a "
                    f"traced value — the first batched flush raises "
                    f"TracerBoolConversionError and the class "
                    f"permanently falls back to per-task dispatch "
                    f"(use jnp.where / lax.cond)",
                    where, severity="warn"))


def _assigned_names(tree: pyast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in pyast.walk(tree):
        targets: List[pyast.AST] = []
        if isinstance(node, pyast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (pyast.AugAssign, pyast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            for el in pyast.walk(t):
                if isinstance(el, pyast.Name):
                    out.add(el.id)
                elif isinstance(el, pyast.Subscript) and \
                        isinstance(el.value, pyast.Name):
                    out.add(el.value.id)  # A[...] = / A[...] +=
    return out


def _aliased_tiles(tc: TaskClassAST) -> List[Tuple[str, str, str]]:
    """Pairs of non-CTL flows whose in-deps read a textually identical
    memory tile: (flow_a, flow_b, "coll(args)")."""
    def norm(t) -> Optional[str]:
        if t is None or t.kind != "memory":
            return None
        args = []
        for a in t.args:
            if isinstance(a, RangeExpr):
                return None  # broadcast range: not a single tile
            args.append(a.src.replace(" ", ""))
        return f"{t.collection}({','.join(args)})"

    tiles: List[Tuple[str, str]] = []
    for f in tc.flows:
        if f.is_ctl:
            continue
        for d in f.deps_in():
            for t in (d.target, d.alt_target):
                key = norm(t)
                if key is not None:
                    tiles.append((f.name, key))
    out: List[Tuple[str, str, str]] = []
    for i, (fa, ka) in enumerate(tiles):
        for fb, kb in tiles[i + 1:]:
            if ka == kb and fa != fb:
                out.append((fa, fb, ka))
    return out


def _prologue_names(jdf: JDFFile) -> Set[str]:
    """Names the JDF's prologue binds to objects made anew for every
    taskpool (its blocks run once per ``new()``): functions, classes,
    assigned values, ``from m import f``.  ``import m`` binds a module,
    which a token names by the attributes read through it."""
    out: Set[str] = set()
    for block in jdf.prologue:
        try:
            tree = pyast.parse(textwrap.dedent(block))
        except SyntaxError:
            continue
        for node in tree.body:
            if isinstance(node, (pyast.FunctionDef, pyast.AsyncFunctionDef,
                                 pyast.ClassDef)):
                out.add(node.name)
            elif isinstance(node, pyast.ImportFrom):
                out.update(a.asname or a.name for a in node.names)
        out |= _assigned_names(tree)
    return out


def _unnameable_read(tree: pyast.AST, shadowed: Set[str],
                     collections: Set[str], prologue: Set[str]
                     ) -> Optional[str]:
    """What in a device body keeps its class from a ``cache_token``, as
    far as the source can tell (the values are only known at run time);
    None when nothing does."""
    reads = body_reads(tree)
    if reads is None:
        return "an import of its own or eval/exec/globals"
    for nm in sorted(set(reads) - shadowed):
        if nm in collections:
            return f"the collection {nm!r}"
        if nm in prologue:
            return f"{nm!r}, which the prologue defines"
    return None


def check_jdf_bodies(jdf: JDFFile, name: Optional[str] = None
                     ) -> List[Finding]:
    """Lint every accelerator BODY of a parsed JDF."""
    name = name or jdf.name
    findings: List[Finding] = []
    collections = {g.name for g in jdf.globals
                   if g.properties.get("type", "").lower() == "collection"}
    prologue = _prologue_names(jdf)
    for tc in jdf.task_classes:
        flow_names = [f.name for f in tc.flows if not f.is_ctl]
        written = [f.name for f in tc.flows
                   if not f.is_ctl and f.access in ("RW", "WRITE")]
        for fa, fb, tile in _aliased_tiles(tc):
            findings.append(Finding(
                "BDY204",
                f"{tc.name}: flows {fa!r} and {fb!r} read the same tile "
                f"{tile} — the same device buffer sits at two argument "
                f"slots, so buffer donation (device_donate) is "
                f"suppressed for every dispatch of this class",
                f"{name} {tc.name}", severity="warn"))
        for b in tc.bodies:
            if b.device_type in ("cpu", "recursive"):
                continue  # host bodies: everything here is legal
            where = f"{name}:{b.line} {tc.name}.BODY" if b.line else \
                f"{name} {tc.name}.BODY"
            label = f"{tc.name} BODY[{b.device_type}]"
            try:
                tree = pyast.parse(b.code)
            except SyntaxError as exc:
                findings.append(Finding(
                    "BDY200", f"{label}: body is not valid Python: {exc}",
                    where))
                continue
            if "this_task" in _names_under(tree):
                findings.append(Finding(
                    "BDY201",
                    f"{label}: reads this_task (per-task runtime "
                    f"state) — the class NEVER batches: no batch_spec "
                    f"is built, every instance pays the per-task dyld "
                    f"dispatch", where, severity="warn"))
            _check_traced_source(tree, where, label, flow_names, findings)
            why = _unnameable_read(
                tree, set(flow_names) | {ld.name for ld in tc.locals},
                collections, prologue)
            if why is not None:
                findings.append(Finding(
                    "BDY206",
                    f"{label}: reads {why} — the program cache cannot "
                    f"name that by value, so the class gets no "
                    f"cache_token and every taskpool traces, lowers and "
                    f"loads its own copies of the class's stacked "
                    f"programs", where, severity="warn"))
            if written and not (_assigned_names(tree) & set(written)):
                findings.append(Finding(
                    "BDY205",
                    f"{label}: never assigns any written flow "
                    f"({', '.join(written)}) — the staged-out result "
                    f"is the unmodified input", where, severity="warn"))
    return findings


def check_function(fn: Callable | str, name: Optional[str] = None,
                   device: bool = True) -> List[Finding]:
    """Lint a DTD task function (or raw function source) with the same
    trace-safety predicates.  ``device=True`` assumes the function runs
    as a device chore (``add_chore``/jitted body) where trace safety
    matters; host-only task functions can pass ``device=False`` to get
    only the nondeterminism checks."""
    if callable(fn):
        label = name or getattr(fn, "__name__", "task_fn")
        try:
            src = textwrap.dedent(inspect.getsource(fn))
        except (OSError, TypeError):
            return [Finding("BDY200", f"{label}: source unavailable "
                            f"(lambda/builtin?)", label, severity="note")]
    else:
        src = textwrap.dedent(fn)
        label = name or "task_fn"
    try:
        tree = pyast.parse(src)
    except SyntaxError as exc:
        return [Finding("BDY200", f"{label}: not valid Python: {exc}",
                        label)]
    findings: List[Finding] = []
    # DTD payload args: the function's positional parameters stand in
    # for flow payloads
    params: List[str] = []
    for node in pyast.walk(tree):
        if isinstance(node, (pyast.FunctionDef, pyast.AsyncFunctionDef)):
            params = [a.arg for a in node.args.args]
            break
    if "this_task" in params or "this_task" in _names_under(tree):
        findings.append(Finding(
            "BDY201", f"{label}: reads this_task — the class never "
            f"batches (per-task dispatch only)", label, severity="warn"))
    if device:
        _check_traced_source(tree, label, label, params, findings)
    else:
        dev_findings: List[Finding] = []
        _check_traced_source(tree, label, label, params, dev_findings)
        findings.extend(f for f in dev_findings if f.code == "BDY203")
    return findings
