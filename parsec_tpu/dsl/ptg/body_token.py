"""What a PTG device body reads, as a value: the ``cache_token`` of the
class's stacked programs (devices/batching.py).

A stacked program traces the body once and is then a function of its
array arguments alone, so it may serve every taskpool of the process
whose body would trace to the same thing.  What the trace depends on is
what the body READS: its source, the class's flow names, and the value
each global name resolves to.  :func:`body_token` names exactly that, by
value, and builds the traceable ``call`` from the token's own contents,
so the cached program holds no taskpool (no ``global_env``, no
collection, no tile).  A body reading something that cannot be named by
value gets no token and its programs stay with its taskpool.

How a global goes in:

- a scalar (``NT`` in ``k < NT - 1``), or a tuple of them (a stencil's
  weights): name, type and ``repr``;
- a module (``ops``, ``jnp``): BY THE ATTRIBUTES THE BODY REACHES
  THROUGH IT, resolved to the objects — ``ops.potrf`` is
  (``"ops.potrf"``, the function), never the module, so replacing
  ``ops.potrf`` names a different program (the rule
  ``batching.KernelsNamedFor`` keys its clones on);
- anything else (a collection, an array, a function the JDF's prologue
  defined: a new object every taskpool), a module used other than
  through an attribute, an ``import`` in the body, or a use of
  ``eval`` / ``exec`` / ``globals`` and their kin: no token.
"""
from __future__ import annotations

import ast
import types
from typing import Any, Callable, Dict, Optional, Sequence, Set, Tuple

__all__ = ["body_reads", "body_token"]

#: values that go into a token by type and repr
SCALARS = (bool, int, float, complex, str, bytes, type(None))

#: builtins through which a body reads names the source does not show
OPAQUE = frozenset({"eval", "exec", "globals", "locals", "vars",
                    "compile", "__import__"})

Reads = Dict[str, Set[Tuple[str, ...]]]


def body_reads(tree: ast.AST) -> Optional[Reads]:
    """Every name the body (and any code nested in it) loads, with the
    attribute paths reached through it: ``ops.linalg.potrf(T)`` gives
    ``{"ops": {("linalg", "potrf")}, "T": {()}}``; ``()`` is the bare
    name.  ``None`` when the source cannot say what the body reads (an
    ``import`` statement, a name in :data:`OPAQUE`)."""
    reads: Reads = {}

    def visit(node: ast.AST) -> bool:
        path = []
        root = node
        while isinstance(root, ast.Attribute):
            path.append(root.attr)
            root = root.value
        if isinstance(root, ast.Name):
            if root.id in OPAQUE:
                return False
            if not isinstance(root.ctx, ast.Store) or path:
                reads.setdefault(root.id, set()).add(tuple(reversed(path)))
            return True
        if isinstance(root, (ast.Import, ast.ImportFrom)):
            return False
        if isinstance(root, ast.AugAssign) \
                and isinstance(root.target, ast.Name):
            reads.setdefault(root.target.id, set()).add(())  # x += 1
        return all(visit(child) for child in ast.iter_child_nodes(root))

    return reads if visit(tree) else None


def _by_value(obj: Any) -> bool:
    """A scalar, or a tuple of values that go in by value."""
    return isinstance(obj, SCALARS) or (
        isinstance(obj, tuple) and all(_by_value(o) for o in obj))


def _nameable(obj: Any) -> bool:
    if _by_value(obj):
        return True
    if not callable(obj):
        return False
    try:
        hash(obj)
    except TypeError:
        return False
    return True


def _entry(dotted: str, obj: Any) -> Tuple:
    if _by_value(obj):
        return (dotted, type(obj), repr(obj))   # -0.0 is not 0.0, nan is nan
    return (dotted, obj)


def _resolve(reads: Reads, global_env: Dict[str, Any],
             provided: Set[str]) -> Optional[Dict[str, Any]]:
    """dotted name -> object for every global the body reads, or
    ``None`` when one of them cannot be named by value."""
    out: Dict[str, Any] = {}
    for name, paths in reads.items():
        if name in provided or name not in global_env:
            continue    # a flow, a local, a temporary, a builtin
        val = global_env[name]
        if not isinstance(val, types.ModuleType):
            if not _by_value(val):
                return None
            out[name] = val
            continue
        for path in paths:
            obj, dotted = val, name
            for attr in path:
                if not isinstance(obj, types.ModuleType):
                    break
                try:
                    obj = getattr(obj, attr)
                except AttributeError:
                    return None
                dotted += "." + attr
            if isinstance(obj, types.ModuleType) or not _nameable(obj):
                return None
            out[dotted] = obj
    return out


def _env_of(resolved: Dict[str, Any]) -> Dict[str, Any]:
    """The globals a token-built body executes in: ``"ops.potrf"``
    becomes a namespace ``ops`` holding ``potrf`` and nothing else."""
    env: Dict[str, Any] = {}
    for dotted, obj in resolved.items():
        *parents, leaf = dotted.split(".")
        scope = env
        for p in parents:
            ns = scope.get(p)
            if ns is None:
                ns = scope[p] = types.SimpleNamespace()
            scope = vars(ns)
        scope[leaf] = obj
    return env


def body_token(name: str, source: str, code: types.CodeType,
               flows: Sequence[Tuple[int, str]],
               written: Sequence[Tuple[int, str]],
               local_names: Sequence[str], rank: int,
               global_env: Dict[str, Any]
               ) -> Optional[Tuple[Tuple, Callable]]:
    """``(token, call)`` for a device body, or ``None`` when what the
    body reads cannot be named by value.

    ``flows`` / ``written``: the class's non-control flows and the
    written ones among them, as (flow index, name); ``local_names``: the
    task locals the body reads (they travel in the group's ``static``);
    ``global_env``: the taskpool's globals, final.  ``call(bargs,
    static)`` is the traceable body over ``static`` as
    ``_device_batch_spec.extract`` builds it, reading nothing but the
    token's contents.
    """
    try:
        reads = body_reads(ast.parse(source))
    except SyntaxError:
        return None
    if reads is None:
        return None
    import jax.numpy as jnp
    import numpy as np
    flow_name = dict(flows)
    provided = set(flow_name.values()) | set(local_names) | {"es_rank"}
    resolved = _resolve(reads, {**global_env, "jnp": jnp, "np": np},
                        provided)
    if resolved is None:
        return None
    reads_rank = "es_rank" in reads
    token = ("ptg-body", name, source, tuple(flows), tuple(written),
             rank if reads_rank else None,
             tuple(sorted((_entry(d, o) for d, o in resolved.items()),
                          key=lambda e: e[0])))
    base = _env_of(resolved)
    if reads_rank:
        base["es_rank"] = rank
    written = tuple(written)

    def call(bargs, static):
        loc, absent, fidx, out_present = static
        env = dict(base)
        env.update(loc)
        for nm in absent:
            env[nm] = None
        for a, i in zip(bargs, fidx):
            env[flow_name[i]] = a
        exec(code, env)
        return tuple(env[nm] for i, nm in written if i in out_present)

    return token, call
