"""PTG runtime: JDF AST → executable task classes ("the generated code").

Reference behavior reproduced from the jdf2c code generator
(ref: parsec/interfaces/ptg/ptg-compiler/jdf2c.c): the taskpool constructor
``parsec_<name>_new(globals...)`` (jdf2c.c:4576), the startup-task enumerator
walking the iteration space for tasks with no task-sourced inputs
(jdf2c.c:2975-3385), ``iterate_successors`` evaluating guards/ranges per out
dep (jdf2c.c:44), ``release_deps`` updating the dynamic dependency hash
table and building the ready ring (jdf2c.c:7161; dynamic dep management is
the default, ptg-compiler/main.c:37), per-device BODY hooks incl. the
accelerator chore (jdf2c.c:6557), and inline expressions (jdf2c.c:8038).

TPU-native notes: BODY code is Python; ``BODY [type=tpu]`` code runs under
the XLA device module — flow names are bound to device arrays and the code's
final assignments to written flow names become the staged-out results.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import types
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ...core.hashtable import HashTable
from ...profiling.grapher import grapher
from ...data.data import Coherency, Data, DataCopy, FlowAccess
from ...data.datatype import Datatype, dtt_of_array
from ...data.data import is_device_array as _is_dev_arr
from ...data.reshape import ReshapeRepo, reshape_array as reshape_to
from ...runtime.scheduling import schedule_keep_best
from ...runtime.taskpool import (Chore, Flow, HookReturn, Task, TaskClass,
                                 Taskpool)
from ...utils import logging as plog
from ...utils.params import params
from .ast import (BodyAST, DepAST, DepTarget, Expr, FlowAST, JDFFile,
                  LocalDef, RangeExpr, TaskClassAST)

_ACCESS_MAP = {"RW": FlowAccess.RW, "READ": FlowAccess.READ,
               "WRITE": FlowAccess.WRITE, "CTL": FlowAccess.NONE}


class _DepEntry:
    """Dynamic dependency-tracking entry (ref: parsec_hashable_dependency_t,
    parsec/parsec_internal.h:229)."""

    __slots__ = ("remaining", "bindings", "spawned")

    def __init__(self, goal: int) -> None:
        self.remaining = goal
        self.bindings: Dict[str, Any] = {}   # flow name -> DataCopy
        self.spawned = False


class PTGTaskClass(TaskClass):
    """One generated task class bound to a PTGTaskpool instance."""

    def __init__(self, tp: "PTGTaskpool", ast: TaskClassAST, tc_id: int) -> None:
        flows = [Flow(f.name, _ACCESS_MAP[f.access], i, ctl=f.is_ctl)
                 for i, f in enumerate(ast.flows)]
        super().__init__(ast.name, tc_id, len(flows), flows=flows)
        self.tp = tp
        self.ast = ast
        self.dep_table = HashTable()
        # generated specializations (the jdf2c analog, codegen.py);
        # interpreted AST walk below remains the fallback
        self._gen_goal = self._gen_succ = None
        if params.get("ptg_codegen"):
            try:
                from .codegen import build_fns
                self._gen_goal, self._gen_succ = build_fns(ast, tp.global_env)
            except Exception as exc:  # pragma: no cover - defensive
                plog.debug.verbose(
                    1, "ptg codegen failed for %s (%s); interpreting",
                    ast.name, exc)
        # flows some in-dep of which declares a datatype ([type=...],
        # [type_remote=...], [type_data=...]): flow name -> (index,
        # flow); only these pass through the reshape engine
        self._typed_in = {
            f.name: (i, f) for i, f in enumerate(ast.flows)
            if not f.is_ctl and any(
                k in d.properties for d in f.deps_in()
                for k in ("type", "type_remote", "type_data"))}
        self.prepare_input = self._prepare_input
        self.release_deps = self._release_deps
        self.iterate_successors = self._iterate_successors
        self.key_fn = lambda locals_: (tc_id, locals_)
        self.prepare_output = lambda es, task: tp.writeback_outputs(es, task)
        self.incarnations = self._build_chores(ast.bodies)

    # ------------------------------------------------------------------ #
    # iteration space                                                    #
    # ------------------------------------------------------------------ #
    def env_of(self, locals_: Tuple) -> Dict[str, Any]:
        """globals + named locals (incl. derived) for an instance."""
        env = dict(self.tp.global_env)
        it = iter(locals_)
        for ld in self.ast.locals:
            if ld.range is not None:
                env[ld.name] = next(it)
            else:
                env[ld.name] = ld.expr(env)
        return env

    def iter_space(self) -> Iterator[Tuple]:
        """Walk the (range) locals' iteration space in definition order;
        later ranges/derived locals may depend on earlier ones
        (ref: jdf2c startup loops)."""
        locals_ = self.ast.locals

        def rec(li: int, env: Dict[str, Any], acc: List[int]):
            if li == len(locals_):
                yield tuple(acc)
                return
            ld = locals_[li]
            if ld.range is None:
                env[ld.name] = ld.expr(env)
                yield from rec(li + 1, env, acc)
                return
            for v in ld.range.values(env):
                env2 = dict(env)
                env2[ld.name] = v
                acc.append(v)
                yield from rec(li + 1, env2, acc)
                acc.pop()

        yield from rec(0, dict(self.tp.global_env), [])

    def rank_of_instance(self, env: Dict[str, Any]) -> int:
        if self.ast.affinity_collection is None:
            return self.tp.rank
        coll = self.tp.global_env[self.ast.affinity_collection]
        args = [a(env) for a in self.ast.affinity_args]
        return coll.rank_of(*args)

    # ------------------------------------------------------------------ #
    # dependency analysis per instance                                   #
    # ------------------------------------------------------------------ #
    def input_goal(self, env: Dict[str, Any]) -> int:
        """#input deps that resolve to task sources (activation count).

        A ranged input target (CTL gather, ``ctl <- ctl R( 0 .. N )``)
        produces one activation per expanded predecessor instance, so the
        goal must count the expansion, not the dep line (ref: generated
        dependency counters cover each control-gather edge, jdf2c.c)."""
        goal = 0
        for f in self.ast.flows:
            for d in f.deps_in():
                t = d.resolve(env)
                if t is not None and t.kind == "task":
                    goal += sum(1 for _ in _expand_args(t.args, env))
        return goal

    def goal_of(self, locals_: Tuple, env: Optional[Dict[str, Any]] = None) -> int:
        """input_goal via the generated counter when available."""
        if self._gen_goal is not None:
            return self._gen_goal(locals_)
        return self.input_goal(env if env is not None else self.env_of(locals_))

    def is_startup(self, locals_: Tuple,
                   env: Optional[Dict[str, Any]] = None) -> bool:
        return self.goal_of(locals_, env) == 0

    # ------------------------------------------------------------------ #
    # task lifecycle                                                     #
    # ------------------------------------------------------------------ #
    def make_task(self, locals_: Tuple, entry: Optional[_DepEntry]) -> Task:
        env = self.env_of(locals_)
        prio = int(self.ast.priority(env)) if self.ast.priority is not None else 0
        task = Task(self.tp, self, locals_, priority=prio)
        if entry is not None:
            for fname, copy in entry.bindings.items():
                fl = self.ast.flow_by_name(fname)
                idx = self.ast.flows.index(fl)
                task.data[idx].data_in = copy
                task.data[idx].fulfilled = True
        return task

    def _prepare_input(self, es, task: Task) -> HookReturn:
        """Bind memory-sourced inputs; task-sourced ones arrived with the
        activation (ref: generated data_lookup, jdf2c.c:42)."""
        env = self.env_of(task.locals)
        for i, f in enumerate(self.ast.flows):
            ref = task.data[i]
            if ref.fulfilled or f.is_ctl:
                continue
            deps_in = f.deps_in()
            if not deps_in:
                # pure-output flow: write-into-memory target or NEW scratch
                ref.data_in = self._output_binding(f, env, es)
                ref.fulfilled = True
                continue
            bound = False
            for d in deps_in:
                t = d.resolve(env)
                if t is None:
                    continue
                if t.kind == "memory":
                    coll = self.tp.global_env[t.collection]
                    args = [a(env) for a in t.args]
                    data = coll.data_of(*args)
                    hc = self.tp.host_copy_of(es, data)
                    if self._flow_masked_writeback(f, env):
                        # a region-masked [type_data] writeback must see
                        # the destination's OLD out-of-region values —
                        # the body may not mutate the home buffer. The
                        # clone detaches from the Data, so the newest
                        # version must land on host FIRST (the lazy
                        # already-home path may have left it on a device;
                        # a stale snapshot here is silent wrong results)
                        hc = _detached_clone(
                            self.tp.pull_newest_to_host(es, data))
                    ref.data_in = hc
                    ref.fulfilled = True
                elif t.kind == "new":
                    ref.data_in = self.tp.new_scratch_copy(f, env)
                    ref.fulfilled = True
                elif t.kind == "null":
                    ref.data_in = None
                    ref.fulfilled = True
                bound = True
                break
            if not bound and not ref.fulfilled:
                # every input dep's guard evaluated false with no
                # alternative: a NULL input (reference: a guarded dep with
                # no ':' alternative yields NULL in that instance;
                # DepAST.resolve returns None, parser.py `cond ? a` form)
                ref.data_in = None
                ref.fulfilled = True
        # reshape pass: a consumer-declared [type=...] differing from the
        # producer's datatype converts through a shared reshape promise —
        # activation-sourced (remote) and memory/task-sourced (local) flows
        # alike (ref: parsec_reshape.c; receiver-side datatype lookup,
        # remote_dep_mpi.c:766)
        # (a local producer's release already handed a typed flow its
        # converted copy, with a use counted on it: nothing left to do)
        for i, f in self._typed_in.values():
            ref = task.data[i]
            if ref.data_in is None:
                continue
            dtt = self._input_dtt(f, env, ref.data_in)
            if dtt is not None:
                ref.data_in = self.tp.reshape_repo.reshaped_copy(
                    ref.data_in, dtt, es)
        return HookReturn.DONE

    def _input_dtt(self, f: FlowAST, env: Dict[str, Any], copy):
        """The datatype this instance's input edge declares, or None.

        The first in-dep applicable under ``env`` is the edge that bound
        the input (same rule as the binding loop — SPMD-consistent on
        both ends of a remote edge). Property semantics mirror the
        reference (parsec_reshape.c; tests/collections/reshape/):
        - ``type``        — LOCAL reshape: consumers get a converted copy
                            regardless of where the data came from;
        - ``type_remote`` — wire datatype only: applied when the
                            producer lives on ANOTHER rank, ignored for
                            local edges (local_no_reshape /
                            avoidable_reshape semantics);
        - ``type_data``   — datatype when reading from the matrix
                            (memory-sourced edges)."""
        for d in f.deps_in():
            t = d.resolve(env)
            if t is None:
                continue
            props = d.properties
            if t.kind == "memory":
                tname = props.get("type_data") or props.get("type")
            elif t.kind == "task":
                tname = props.get("type")
                if tname is None:
                    rname = props.get("type_remote")
                    if rname is not None and self._edge_is_remote(t, env):
                        tname = rname
            else:
                tname = props.get("type")
            if tname is None:
                return None
            return self.resolve_dtt_name(tname, copy, f.name)
        return None

    def producer_rank_of(self, t, env: Dict[str, Any]) -> Optional[int]:
        """Rank of a task-sourced dep target's FIRST expanded producer
        instance; None when unresolvable. Shared by _edge_is_remote and
        the distributed wave's wire-type decision — both ends of an
        edge must resolve identically (the reference's both-ends
        remote_dep_mpi_retrieve_datatype lookup)."""
        try:
            ptc = self.tp.class_by_name(t.task_class)
            args = next(iter(_expand_args(t.args, env)))
            penv = ptc.env_of(ptc.ast.locals_from_param_args(args))
            return ptc.rank_of_instance(penv)
        except (KeyError, StopIteration):
            return None

    def _edge_is_remote(self, t, env: Dict[str, Any]) -> bool:
        """Does this task-sourced in-dep cross ranks?"""
        if self.tp.nb_ranks == 1:
            return False
        pr = self.producer_rank_of(t, env)
        return pr is not None and pr != self.tp.rank

    def resolve_dtt_name(self, tname: str, copy, flow_name: str) -> Datatype:
        """A [type*=NAME] property: a Datatype global, or one of the
        region shorthands applied to the copy's base type."""
        val = self.tp.global_env.get(tname)
        if isinstance(val, Datatype):
            return val
        if tname in ("lower", "upper", "full"):
            base = (copy.dtt if copy is not None and copy.dtt is not None
                    else dtt_of_array(copy.payload))
            return dataclasses.replace(base, region=tname)
        raise TypeError(
            f"{self.name}.{flow_name}: [type={tname}] is neither a "
            f"Datatype global nor a region shorthand")

    def _flow_masked_writeback(self, f: FlowAST, env: Dict[str, Any]) -> bool:
        """Does any memory out-dep of this flow declare a (possibly
        region-masked) writeback type? Those flows bind detached clones
        so the body cannot clobber the destination's out-of-region
        values before the masked writeback runs."""
        for d in f.deps_out():
            t = d.resolve(env)
            if t is None or t.kind != "memory":
                continue
            nm = d.properties.get("type_data") or d.properties.get("type")
            if nm is not None and nm != "full":
                return True
        return False

    def _output_binding(self, f: FlowAST, env: Dict[str, Any], es=None):
        """WRITE-only flow: bind to its memory out-target or a NEW buffer."""
        for d in f.deps_out():
            t = d.resolve(env)
            if t is not None and t.kind == "memory":
                coll = self.tp.global_env[t.collection]
                args = [a(env) for a in t.args]
                data = coll.data_of(*args)
                hc = self.tp.host_copy_of(None, data)
                if self._flow_masked_writeback(f, env):
                    # detached snapshot: sync the newest version home
                    # first (see _prepare_input's masked-writeback note)
                    hc = _detached_clone(
                        self.tp.pull_newest_to_host(es, data))
                return hc
        return self.tp.new_scratch_copy(f, env)

    def _iterate_successors(self, es, task: Task, cb: Callable) -> None:
        """cb(succ_tc, succ_locals, succ_flow_name, copy, out_flow_idx) per
        satisfied output edge (ref: generated iterate_successors)."""
        if self._gen_succ is not None:
            copies = [None if f.is_ctl
                      else (task.data[i].data_out or task.data[i].data_in)
                      for i, f in enumerate(self.ast.flows)]
            resolve = self.tp.class_by_name
            self._gen_succ(
                task.locals, copies,
                lambda name, loc, fl, cp, idx, tys=None: cb(
                    resolve(name), loc, fl, cp, idx, tys))
            return
        env = self.env_of(task.locals)
        for i, f in enumerate(self.ast.flows):
            copy = None if f.is_ctl else (task.data[i].data_out or task.data[i].data_in)
            for d in f.deps_out():
                t = d.resolve(env)
                if t is None or t.kind in ("null", "new"):
                    continue
                if t.kind == "memory":
                    continue  # handled in prepare_output (writeback)
                lt = d.properties.get("type")
                succ_tc = self.tp.class_by_name(t.task_class)
                for succ_locals in _expand_args(t.args, env):
                    cb(succ_tc, succ_locals, t.flow, copy, i, lt)

    def _release_deps(self, es, task: Task, action_mask: int) -> List[Task]:
        """Local successors activate in place; remote ones accumulate into a
        per-rank batch handed to the comm engine as one activation per output
        flow (ref: parsec_remote_deps_t accumulation, remote_dep.h:143-160).

        With static dep management active the whole walk is ONE native
        call: the lowered CSR edges route copies and decrement dense
        counters in C (ref: --dep-management=index-array)."""
        repo = self.tp.reshape_repo
        for i, _f in self._typed_in.values():
            # this task has read its converted inputs: the uses its
            # producers took for it go back, and a conversion's last
            # reader drops it (a flow the task wrote hands its copy on)
            repo.release(task.data[i].data_in,
                         drop=self.flows[i].access == FlowAccess.READ)
        if self.tp._engine is not None:
            copies = tuple(
                None if f.is_ctl
                else (task.data[i].data_out or task.data[i].data_in)
                for i, f in enumerate(self.ast.flows))
            tid = self.tp._dag.id_of[(self.ast.name, task.locals)]
            return [self.tp._make_task_static(r)
                    for r in self.tp._engine.complete(tid, copies)]
        ready: List[Task] = []
        remote_edges: Dict[int, List[Tuple]] = {}
        flow_payloads: Dict[int, Any] = {}
        flow_dtts: Dict[int, Any] = {}
        # converted copies handed out below: this release holds a use
        # of its own on each until it has walked every successor, so
        # that an early reader's completion on another thread cannot
        # drop a copy a later one still gets
        held: Dict[int, Any] = {}

        def activate(succ_tc: "PTGTaskClass", succ_locals: Tuple,
                     flow_name: str, copy, out_idx: int,
                     edge_type=None) -> None:
            if grapher.enabled:
                # must match Task.snprintf() so DOT edges hit real nodes
                grapher.dep(task, f"{succ_tc.name}"
                            f"({', '.join(map(str, succ_locals))})", flow_name)
            env = succ_tc.env_of(succ_locals)
            dst = succ_tc.rank_of_instance(env)
            if dst == self.tp.rank:
                if edge_type is not None and copy is not None:
                    # [type=...] on the OUT dep: producer-side local
                    # reshape — successors receive the converted copy
                    # (local_output_reshape semantics)
                    dtt = self.resolve_dtt_name(edge_type, copy, flow_name)
                    copy = repo.reshaped_copy(copy, dtt, es)
                elif flow_name in succ_tc._typed_in and copy is not None:
                    # [type=...] on the successor's IN dep: converted
                    # HERE, where the tile is produced, once for all
                    # the successors that declare the type
                    dtt = succ_tc._input_dtt(
                        succ_tc._typed_in[flow_name][1], env, copy)
                    if dtt is not None:
                        conv = repo.acquire(copy, dtt, es)
                        if conv is not copy and id(conv) not in held:
                            held[id(conv)] = conv
                            repo.retain(conv)
                        copy = conv
                t = succ_tc.activate(succ_locals, flow_name, copy)
                if t is not None:
                    ready.append(t)
                return
            if self.tp.comm is None:
                raise RuntimeError(
                    f"{self.tp.name}: task {task.snprintf()} has a remote "
                    f"successor {succ_tc.name}{succ_locals} but no comm "
                    f"engine is attached (nb_ranks={self.tp.nb_ranks})")
            remote_edges.setdefault(dst, []).append(
                (succ_tc.task_class_id, succ_locals, flow_name, out_idx))
            if out_idx not in flow_payloads and copy is not None:
                ce = getattr(self.tp.comm, "ce", None)
                plane = getattr(ce, "device_plane", None)
                # mesh-local peers (one XLA client) take device buffers
                # by reference — offering the device copy here is what
                # lets remote_dep's fast path skip the D2H sync below
                mesh_local = (getattr(self.tp.comm, "_mesh_local", False)
                              and ce is not None
                              and ce.mesh_local_with(dst))
                newest = (copy.data.newest_copy()
                          if copy.data is not None else copy)
                if (plane is not None or mesh_local) \
                        and newest is not None \
                        and newest.payload is not None \
                        and _is_dev_arr(newest.payload):
                    # device data plane attached and the newest version
                    # lives on device: ship the device buffer itself —
                    # the consumer pulls it device-to-device, no D2H
                    flow_payloads[out_idx] = newest.payload
                    flow_dtts[out_idx] = newest.dtt
                elif copy.data is not None:
                    host = copy.data.sync_to_host(es.context.devices)
                    flow_payloads[out_idx] = np.asarray(host.payload)
                    flow_dtts[out_idx] = host.dtt
                else:
                    flow_payloads[out_idx] = np.asarray(copy.payload)
                    flow_dtts[out_idx] = copy.dtt  # rides the wire: a
                    # matching consumer type must not reconvert

        self._iterate_successors(es, task, activate)
        for conv in held.values():
            repo.release(conv)
        if remote_edges:
            self.tp.comm.activate_batch(self.tp, task, flow_payloads,
                                        remote_edges, flow_dtts)
        return ready

    def activate(self, locals_: Tuple, flow_name: str, copy) -> Optional[Task]:
        """One input of instance ``locals_`` became available; spawn the task
        when the dynamic dep counter reaches its goal."""
        sc = self.tp._stagec
        if sc is not None:
            # stage-compile seam (stagec/, ISSUE 12): activations for
            # instances fused into a compiled stage count toward the
            # STAGE's external goal instead; local residue, other
            # stages, and remote ranks all arrive through this one
            # funnel, so no wire/protocol change is needed.  Downgraded
            # stages pass through to the dynamic table below.
            handled, task = sc.on_activate(self, locals_, flow_name, copy)
            if handled:
                return task
        key = locals_
        task = None
        self.dep_table.lock_bucket(key)
        try:
            entry = self.dep_table.nolock_find(key)
            if entry is None:
                entry = _DepEntry(self.goal_of(locals_))
                self.dep_table.nolock_insert(key, entry)
            if copy is not None:
                entry.bindings[flow_name] = copy
            entry.remaining -= 1
            assert entry.remaining >= 0, \
                f"{self.name}{locals_}: more activations than inputs"
            if entry.remaining == 0 and not entry.spawned:
                entry.spawned = True
                self.dep_table.nolock_remove(key)
                task = self.make_task(locals_, entry)
        finally:
            self.dep_table.unlock_bucket(key)
        if task is not None and sc is not None:
            # compiled residue schedule (stagec/, ISSUE 13): a ready
            # task of a pre-planned residue group buffers with the
            # compiler and dispatches with its whole group as one
            # device burst — returns None here (routed, not lost)
            task = sc.on_residue_ready(task)
        return task

    # ------------------------------------------------------------------ #
    # bodies → chores                                                    #
    # ------------------------------------------------------------------ #
    def _build_chores(self, bodies: List[BodyAST]) -> List[Chore]:
        chores: List[Chore] = []
        for b in bodies:
            if b.device_type in ("cpu", "recursive"):
                code = compile(b.code, f"<jdf:{self.name}:BODY>", "exec")
                chores.append(Chore("cpu", self._cpu_hook_factory(code)))
            elif b.device_type == "tpu":
                from ...devices.tpu import tpu_chore_hook
                fn, spec = self._device_fn_factory(b)
                chores.append(Chore(b.device_type, tpu_chore_hook(),
                                    dyld_fn=fn, batch_spec=spec))
            else:
                # any other accelerator type routes to its attached
                # device module (ref: per-device-type chore lists,
                # parsec_internal.h:380-437; see devices/template.py)
                from ...devices.template import template_chore_hook
                fn, spec = self._device_fn_factory(b)
                chores.append(Chore(b.device_type,
                                    template_chore_hook(b.device_type),
                                    dyld_fn=fn, batch_spec=spec))
        if not any(c.device_type == "cpu" for c in chores):
            # always provide a host fallback interpreting the first body
            b = bodies[0]
            code = compile(b.code, f"<jdf:{self.name}:BODY>", "exec")
            chores.append(Chore("cpu", self._cpu_hook_factory(code)))
        return chores

    def _body_env(self, task: Task, payloads: Dict[str, Any]) -> Dict[str, Any]:
        env = self.env_of(task.locals)
        env.update(payloads)
        env["es_rank"] = self.tp.rank
        env["this_task"] = task
        try:
            import jax.numpy as jnp
            env["jnp"] = jnp
        except Exception:
            pass
        env["np"] = np
        return env

    def _cpu_hook_factory(self, code):
        def hook(es, task: Task) -> HookReturn:
            payloads = {}
            for i, f in enumerate(self.ast.flows):
                if f.is_ctl:
                    continue
                copy = task.data[i].data_in
                if copy is None:
                    payloads[f.name] = None
                    continue
                if copy.data is not None:
                    # host execution needs the newest version on device 0
                    host = self.tp.pull_newest_to_host(es, copy.data)
                    payloads[f.name] = Data.materialize_host(host)
                    task.data[i].data_in = host
                else:
                    if copy.payload is None and copy.dtt is not None \
                            and self.flows[i].access == FlowAccess.WRITE:
                        # a WRITE-only flow's buffer, first touched by a
                        # host body: it may fill it in place
                        copy.payload = np.zeros(copy.dtt.shape,
                                                copy.dtt.dtype)
                    payloads[f.name] = Data.materialize_host(copy)
            env = self._body_env(task, payloads)
            exec(code, env)
            for i, f in enumerate(self.ast.flows):
                if f.is_ctl or not (self.flows[i].access & FlowAccess.WRITE):
                    continue
                copy = task.data[i].data_in
                if copy is None:
                    continue
                # functional-style bodies (device BODY run as host fallback)
                # rebind the flow name instead of mutating in place: write
                # the rebound value back into the host payload
                new_val = env.get(f.name)
                if new_val is not None and new_val is not copy.payload:
                    arr = np.asarray(new_val)
                    if copy.payload is None:
                        copy.payload = arr
                    else:
                        np.copyto(copy.payload, arr)
                if copy.data is not None:
                    copy.data.version_bump(copy.device_id)
            return HookReturn.DONE
        return hook

    def _device_fn_factory(self, body: BodyAST):
        """Build the accelerator executable: flow names are device arrays;
        assignments to written flow names are returned (in flow order).
        Returns ``(fn, batch_spec)`` — the per-task wrapper plus the
        batched-dispatch recipe (devices/batching.py), or spec=None when
        the body reads per-task runtime state (``this_task``)."""
        code = compile(body.code, f"<jdf:{self.name}:BODY[tpu]>", "exec")
        written = [(i, f.name) for i, f in enumerate(self.ast.flows)
                   if not f.is_ctl and (self.flows[i].access & FlowAccess.WRITE)]

        named: Optional[Dict[str, Any]] = None

        def fn(task: Task, arrays: List[Any]):
            nonlocal named
            payloads = {}
            for i, f in enumerate(self.ast.flows):
                if not f.is_ctl:
                    payloads[f.name] = arrays[i]
            env = self._body_env(task, payloads)
            if named is None:
                # asked at the first task, when the taskpool's globals
                # are final: the kernels a task dispatched alone calls
                # run under the class's name (jit_<CLASS>), as its
                # stacked programs do
                from ...devices.batching import KernelsNamedFor
                named = {
                    nm: KernelsNamedFor(self.tp.global_env[nm], self.name)
                    for nm in code.co_names
                    if isinstance(self.tp.global_env.get(nm),
                                  types.ModuleType)}
            env.update(named)
            exec(code, env)
            return tuple(env[name] for i, name in written
                         if task.data[i].data_in is not None)
        return fn, self._device_batch_spec(body, code, written)

    def _device_batch_spec(self, body: BodyAST, code, written):
        """Batching recipe for a JDF device body: present flow arrays
        form the batch axis; the locals the body actually READS
        (co_names ∩ declared locals) go into the static group key, so
        e.g. every GEMM(k, m, n) of a wave stacks into one dispatch
        (the body references no locals) while a body indexing on ``k``
        still batches within equal ``k``."""
        from ...devices.batching import DeviceBatchSpec
        names = set(code.co_names)
        if "this_task" in names:
            return None   # reads per-task runtime state: never batchable
        nonctl = [(i, f.name) for i, f in enumerate(self.ast.flows)
                  if not f.is_ctl]
        flow_name = dict(nonctl)
        refd = [ld.name for ld in self.ast.locals if ld.name in names]

        def extract(task: Task, arrays: List[Any]):
            bargs: List[Any] = []
            fidx: List[int] = []
            absent: List[str] = []
            for i, nm in nonctl:
                a = arrays[i]
                if a is None:
                    absent.append(nm)
                else:
                    bargs.append(a)
                    fidx.append(i)
            if refd:
                env = self.env_of(task.locals)
                try:
                    loc = tuple((nm, env[nm]) for nm in refd)
                    hash(loc)
                except (KeyError, TypeError):
                    return None
            else:   # body reads no locals: one group per shape signature
                loc = ()
            out_present = tuple(i for i, nm in written
                                if task.data[i].data_in is not None)
            static = (loc, tuple(absent), tuple(fidx), out_present)
            return tuple(bargs), tuple(fidx), static

        def call(bargs, static):
            loc, absent, fidx, out_present = static
            env = dict(self.tp.global_env)
            env.update(loc)
            for nm in absent:
                env[nm] = None
            for a, i in zip(bargs, fidx):
                env[flow_name[i]] = a
            env["es_rank"] = self.tp.rank
            try:
                import jax.numpy as jnp
                env["jnp"] = jnp
            except Exception:
                pass
            env["np"] = np
            exec(code, env)
            return tuple(env[nm] for i, nm in written if i in out_present)

        spec_name = f"{self.name}[{body.device_type}]"

        def late_token():
            # asked at the first stacked dispatch, when the taskpool's
            # globals are final (ops.*_taskpool sets ``ops`` after
            # ``new()``): what the body reads, by value, and a ``call``
            # built from that alone -- or None, and ``call`` above
            # keeps the programs with this taskpool
            from .body_token import body_token
            return body_token(spec_name, body.code, code, nonctl, written,
                              refd, self.tp.rank, self.tp.global_env)

        return DeviceBatchSpec(spec_name, extract, call,
                               late_token=late_token)


def _detached_clone(copy: DataCopy) -> DataCopy:
    """A private host copy of ``copy``'s payload, detached from its Data
    (body mutations stay private until the writeback applies them)."""
    payload = (None if copy is None or copy.payload is None
               else np.array(np.asarray(copy.payload)))
    d = Data(nb_elts=0 if payload is None else payload.size)
    c = DataCopy(d, 0, payload=payload,
                 dtt=None if copy is None else copy.dtt)
    c.version = 1
    c.coherency = Coherency.OWNED
    d.attach_copy(c)
    return c


def _expand_args(args: List[Any], env: Dict[str, Any]) -> Iterator[Tuple]:
    """Expand Expr/RangeExpr argument lists into concrete locals tuples
    (a range arg == broadcast edge, ref Ex05 ``TaskRecv(k, 0 .. NB .. 2)``)."""
    dims: List[List[int]] = []
    for a in args:
        if isinstance(a, RangeExpr):
            dims.append(list(a.values(env)))
        else:
            dims.append([a(env)])
    for combo in itertools.product(*dims):
        yield tuple(combo)


class PTGTaskpool(Taskpool):
    """One instantiated JDF taskpool (ref: the generated
    parsec_<name>_taskpool_t + constructor, jdf2c.c:4576)."""

    def __init__(self, jdf: JDFFile, global_env: Dict[str, Any],
                 rank: int = 0, nb_ranks: int = 1) -> None:
        super().__init__(name=jdf.name, nb_task_classes=len(jdf.task_classes))
        self.jdf = jdf
        self.rank = rank
        self.nb_ranks = nb_ranks
        self.global_env: Dict[str, Any] = {"np": np}
        # run prologue blocks IN global_env (globals == locals, so helper
        # functions can see each other, recurse, and read JDF globals)
        for block in jdf.prologue:
            exec(compile(block, f"<jdf:{jdf.name}:prologue>", "exec"),
                 self.global_env)
        # bind globals: hidden ones take defaults, others must be supplied
        for g in jdf.globals:
            if g.name in global_env:
                self.global_env[g.name] = global_env[g.name]
            elif g.default is not None:
                self.global_env[g.name] = g.default(self.global_env)
            else:
                raise TypeError(f"{jdf.name}: missing global {g.name!r}")
        unknown = set(global_env) - {g.name for g in jdf.globals}
        if unknown:
            raise TypeError(f"{jdf.name}: unknown globals {sorted(unknown)}")
        self._classes: Dict[str, PTGTaskClass] = {}
        for i, tc_ast in enumerate(jdf.task_classes):
            tc = PTGTaskClass(self, tc_ast, i)
            self._classes[tc_ast.name] = tc
            self.task_classes.append(tc)
        self._scratch_lock = threading.Lock()
        self.reshape_repo = ReshapeRepo()
        self.startup_hook = self._startup
        self.nb_local_tasks = 0
        self.comm = None  # remote-dep driver, attached by the comm engine
        self._dag = None      # LoweredDAG when static dep management is on
        self._turbo = None    # TurboRunner when the native loop took it
        self._engine = None   # NativeDAG / PyDAG ready-tracking engine
        self._stagec = None   # StageCompiler when stage_compile is on

    def class_by_name(self, name: str) -> PTGTaskClass:
        return self._classes[name]

    # ------------------------------------------------------------------ #
    # startup (ref: generated startup enumerator jdf2c.c:2975-3385)       #
    # ------------------------------------------------------------------ #
    def _startup(self, context, tp) -> List[Task]:
        if params.get("stage_compile") and not grapher.enabled:
            # whole-stage DAG->XLA compilation (stagec/, ISSUE 12):
            # compilable stages execute as single fused chores, the
            # residue stays on the interpreted path below.  Takes
            # precedence over the static/turbo engines — the compiled
            # stage IS the static fast path here.
            from ...stagec.runtime import try_install
            self._stagec = try_install(self, context)
        if (self._stagec is None
                and params.get("ptg_dep_management") == "static"
                and self.nb_ranks == 1 and not grapher.enabled
                and not self._has_out_edge_types()):
            turbo = self._startup_turbo(context)
            if turbo is not None:
                return turbo
            return self._startup_static()
        total = 0
        startup: List[Task] = []
        sc = self._stagec
        count_foreign = self.nb_ranks > 1 and self.comm is not None
        expected_mem_puts = 0
        if sc is not None:
            # plan-cached startup enumeration (ISSUE 13): the stage
            # plan already walked the full instance space — local
            # totals, goal-0 residue, and the foreign mem-put
            # expectation are pure functions of its identity, so a
            # repeat pool skips the per-instance iteration-space walk
            total = sc.plan.n_local
            expected_mem_puts = sc.plan.startup_mem_puts
            for (name, locals_) in sc.plan.startup_goal0:
                t = self.class_by_name(name).make_task(locals_, None)
                t = sc.on_residue_ready(t)
                if t is not None:
                    startup.append(t)
            # stages with no external task inputs start the DAG (their
            # members are counted in n_local; a stage completion
            # retires every member's count)
            startup.extend(sc.startup_tasks())
        else:
            for tc in self._classes.values():
                for locals_ in tc.iter_space():
                    env = tc.env_of(locals_)
                    if tc.rank_of_instance(env) != self.rank:
                        if count_foreign:
                            # a foreign task whose out-dep targets MY
                            # memory will ship a writeback: hold
                            # termination for it
                            expected_mem_puts += \
                                self._count_mem_puts_to_me(tc, env)
                        continue
                    total += 1
                    if tc.goal_of(locals_, env) == 0:
                        startup.append(tc.make_task(locals_, None))
        # counts FIRST, delivery second: activations/puts released by
        # counts_ready may schedule tasks that complete on a worker
        # thread immediately — nb_tasks must already hold the total or
        # the decrement goes negative (or is overwritten into a hang)
        self.nb_local_tasks = total
        self.set_nb_tasks(total)
        if expected_mem_puts:
            self.add_pending_action(expected_mem_puts)
        if count_foreign:
            # expectations credited: buffered early arrivals may deliver
            self.comm.counts_ready(self)
        if sc is not None:
            # cross-pool chaining (stagec/chain.py, ISSUE 13): when an
            # earlier pool's chained program pre-computed this pool's
            # first stage, adopt its stashed outputs now — AFTER the
            # counts above, so the members' completions cannot go
            # negative.  Successors it releases join the startup set.
            startup.extend(sc.consume_chain(
                context.execution_streams[0]))
        plog.debug.verbose(4, "ptg %s: %d local tasks, %d startup",
                           self.name, total, len(startup))
        return startup

    def _startup_turbo(self, context) -> Optional[List[Task]]:
        """The static mode's native fast path (VERDICT r3 missing #4):
        data binding precompiled into slot tables, select->release in a
        C priority heap, one XLA call per task, lazy device-resident
        writebacks. Falls back to the classic static path (None) when
        the pool is turbo-ineligible (unresolvable slots), unless
        ptg_dispatch=turbo demands it. Runs on a worker claimed from
        the wait loop; errors surface through record_task_error like
        any task-body failure."""
        mode = str(params.get_or("ptg_dispatch", "string", "auto"))
        if mode not in ("auto", "turbo"):
            return None
        tpu_devs = [d for d in context.devices
                    if d.device_type == "tpu"]
        if not tpu_devs:
            if mode == "turbo":
                raise RuntimeError(
                    "ptg_dispatch=turbo demands the native loop but the "
                    "context has no accelerator device module")
            return None
        from .turbo import TurboRunner
        from .wave import WaveError
        try:
            runner = TurboRunner(self)
        except WaveError as exc:
            if mode == "turbo":
                raise
            plog.debug.verbose(
                2, "ptg %s: turbo ineligible (%s); classic static path",
                self.name, exc)
            return None
        dev = tpu_devs[0]
        self._turbo = runner
        n = runner.dag.n_tasks
        self.nb_local_tasks = n
        self.set_nb_tasks(n)

        def _run(es):
            pools = runner.build_pools(device=dev.jax_device)
            runner.execute_per_task(pools, device=dev.jax_device)
            runner.attach_lazy_results(dev.device_index)
            dev.stats["tasks"] += n
            for _ in range(n):
                self.task_completed()

        context.submit_native_loop(_run)
        plog.debug.verbose(4, "ptg %s (turbo): %d tasks queued on the "
                           "native loop", self.name, n)
        return []

    def _startup_static(self) -> List[Task]:
        """Static dep management (ref: --dep-management=index-array):
        lower the task space once into flat arrays + a native counter
        engine; startup = the zero-indegree set. Single-rank only —
        multi-rank and DOT capture stay on the dynamic hash path."""
        from .lower import lower, make_engine
        self._dag = lower(self)
        self._engine = make_engine(self._dag)
        self.nb_local_tasks = self._dag.n_tasks
        self.set_nb_tasks(self._dag.n_tasks)
        startup = [self._make_task_static(t) for t in self._engine.start()]
        plog.debug.verbose(4, "ptg %s (static): %d tasks, %d edges, "
                           "%d startup", self.name, self._dag.n_tasks,
                           self._dag.n_edges, len(startup))
        return startup

    def _has_out_edge_types(self) -> bool:
        """[type=...] on OUT deps reshapes copies during release — the
        static engine routes copies in C without property handling, so
        such taskpools stay on the dynamic path. (type_remote is
        consumer-resolved and does not affect the release walk.)"""
        for tc in self.task_classes:
            for f in tc.ast.flows:
                for d in f.deps_out():
                    if "type" in d.properties:
                        return True
        return False

    def _make_task_static(self, tid: int) -> Task:
        """Spawn a lowered task: class/locals/priority from the flat
        arrays; inputs routed by the engine land in flow order."""
        dag = self._dag
        tc = self.task_classes[int(dag.class_of[tid])]
        task = Task(self, tc, dag.locals_of[tid],
                    priority=int(dag.priority[tid]))
        bindings = self._engine.take_bindings(tid)
        for i in range(len(tc.ast.flows)):
            copy = bindings[i]
            if copy is not None:
                task.data[i].data_in = copy
                task.data[i].fulfilled = True
        return task

    # ------------------------------------------------------------------ #
    # data helpers                                                       #
    # ------------------------------------------------------------------ #
    def host_copy_of(self, es, data: Data) -> DataCopy:
        return data.host_copy()

    def pull_newest_to_host(self, es, data: Data) -> DataCopy:
        if es is None:
            return data.host_copy()
        return data.sync_to_host(es.context.devices)

    def _count_mem_puts_to_me(self, tc: "PTGTaskClass",
                              env: Dict[str, Any]) -> int:
        """#memory out-deps of one FOREIGN instance that land on a tile
        this rank owns (must mirror writeback_outputs' emission)."""
        n = 0
        for i, f in enumerate(tc.ast.flows):
            if f.is_ctl or not (tc.flows[i].access & FlowAccess.WRITE):
                continue
            for d in f.deps_out():
                t = d.resolve(env)
                if t is None or t.kind != "memory":
                    continue
                coll = self.global_env[t.collection]
                if coll.rank_of(*[a(env) for a in t.args]) == self.rank:
                    n += 1
        return n

    def new_scratch_copy(self, f: FlowAST, env: Dict[str, Any]) -> DataCopy:
        """NEW target: a runtime-allocated buffer (ref: arena-backed NEW
        tiles). Shape comes from the flow's [shape=...] property: either
        the ``AxB`` dimension form or (quoted) one Python expression
        evaluating to an int/tuple — instance-dependent shapes like
        partial edge tiles need the latter."""
        shape = scratch_shape(f, env)
        if shape is None:
            raise RuntimeError(
                f"flow {f.name}: NEW target needs a [shape=...] property")
        dt = np.dtype(f_prop(f, "dtype", "float32"))
        if f.access == "WRITE":
            # nothing reads a WRITE-only flow before its task writes it:
            # the body's output is the flow's first value.  No host
            # buffer, no stage-in, nobody's Data (so no LRU keeps it):
            # the copy says what it will hold (``dtt``), a host body
            # that wants a buffer to fill gets one from it
            # (``_cpu_hook_factory``), and it dies with its last reader
            copy = DataCopy(None, 0, payload=None, dtt=Datatype(dt, shape))
            copy.coherency = Coherency.OWNED
            return copy
        data = Data(nb_elts=int(np.prod(shape)))
        copy = DataCopy(data, 0, payload=np.zeros(shape, dtype=dt))
        copy.coherency = Coherency.OWNED
        copy.version = 1
        data.attach_copy(copy)
        return copy

    # memory writeback of out deps targeting collections
    def writeback_outputs(self, es, task: Task) -> None:
        tc: PTGTaskClass = task.task_class
        env = tc.env_of(task.locals)
        for i, f in enumerate(tc.ast.flows):
            if f.is_ctl or not (tc.flows[i].access & FlowAccess.WRITE):
                continue
            copy = task.data[i].data_out or task.data[i].data_in

            # lazy: a D2H pull only when some dep really needs host bytes —
            # the dominant case (tile already home, newest copy on device)
            # must not pay a device->host transfer per task (that
            # serializes the whole DAG on the host link)
            _src_host_cell: List[Any] = []

            def src_host_of():
                if not _src_host_cell:
                    if copy is None or copy.device_id == 0:
                        _src_host_cell.append(copy)
                    elif copy.data is not None:
                        _src_host_cell.append(
                            self.pull_newest_to_host(es, copy.data))
                    else:
                        # detached device copy (Data destructed): no host
                        # source exists; remote path sends a release-only
                        # notification, local path errors loudly below
                        _src_host_cell.append(None)
                return _src_host_cell[0]

            for d in f.deps_out():
                t = d.resolve(env)
                if t is None or t.kind != "memory":
                    continue
                coll = self.global_env[t.collection]
                args = [a(env) for a in t.args]
                dst_rank = coll.rank_of(*args)
                if dst_rank != self.rank:
                    # cross-rank memory writeback: ship to the owner, who
                    # counted this arrival as a pending runtime action at
                    # startup; a copy-less flow still sends a release-only
                    # notification so the owner's count retires (the
                    # static count cannot see dynamic copy-None)
                    assert self.comm is not None, \
                        "remote memory target without a comm engine"
                    sh = src_host_of()
                    payload = sh.payload if sh is not None else None
                    self.comm.mem_writeback(self, t.collection, tuple(args),
                                            payload, dst_rank)
                    continue
                if copy is None:
                    continue
                # [type_data=...] / [type=...] on a memory OUT dep: only
                # the declared region's elements land in memory, the rest
                # of the destination tile keeps its old values (ref:
                # local_input_reshape.jdf WRITE_A -> descA [type=LOWER])
                wb_name = (d.properties.get("type_data")
                           or d.properties.get("type"))
                if wb_name is not None and copy is not None:
                    # a no-op annotation ([type=full] / a full-region
                    # Datatype with the copy's own dtype) must NOT
                    # defeat the lazy already-home path below — that
                    # would force a per-task D2H pull
                    if wb_name == "full":
                        wb_name = None
                    else:
                        val = self.global_env.get(wb_name)
                        pdt = getattr(copy.payload, "dtype", None)
                        if (isinstance(val, Datatype)
                                and val.region == "full" and pdt is not None
                                and np.dtype(val.dtype) == np.dtype(pdt)):
                            wb_name = None
                dest = coll.data_of(*args)
                if copy.data is dest and wb_name is None:
                    # already home: the Data owns the newest (device) copy;
                    # do NOT force a device->host transfer here — readers
                    # sync lazily (a per-task d2h pull would serialize the
                    # DAG on transfer latency)
                    continue
                sh = src_host_of()
                if sh is None:
                    raise RuntimeError(
                        f"{task.snprintf()}: memory writeback of flow "
                        f"{f.name} from a detached device copy")
                src_arr = np.asarray(sh.payload)
                mask = None
                if wb_name is not None:
                    dtt = tc.resolve_dtt_name(wb_name, sh, f.name)
                    src_arr = np.asarray(reshape_to(src_arr, dtt))
                    mask = dtt.mask()
                # a masked writeback preserves the destination's
                # out-of-region values — those must be the NEWEST ones,
                # which may live on a device (the lazy already-home path);
                # an unmasked writeback fully overwrites, so the plain
                # host copy suffices
                dh = (self.pull_newest_to_host(es, dest) if mask is not None
                      else self.host_copy_of(es, dest))
                if dh.payload is None:
                    dh.payload = np.array(src_arr)
                elif mask is None:
                    np.copyto(dh.payload, src_arr)
                else:
                    np.copyto(dh.payload, src_arr, where=mask)
                dest.version_bump(0)


def f_prop(f: FlowAST, key: str, default: str) -> str:
    for d in f.deps:
        if key in d.properties:
            return d.properties[key]
    return default


def scratch_shape(f: FlowAST, env: Dict[str, Any]) -> Optional[Tuple[int, ...]]:
    """Shape a flow's [shape=...] property declares for this instance
    (``AxB`` dims or one Python expression -> int/tuple), or None when
    the property is absent. Shared by the runtime's NEW allocation and
    wave scratch pools so both accept the same JDFs."""
    shape_src = None
    for d in f.deps:
        if "shape" in d.properties:
            shape_src = d.properties["shape"]
            break
    if shape_src is None:
        return None
    try:
        val = Expr(shape_src)(env)
    except (SyntaxError, NameError, TypeError):
        val = None
    if isinstance(val, (tuple, list)):
        return tuple(int(v) for v in val)
    if isinstance(val, (int, np.integer)):
        return (int(val),)
    return tuple(int(Expr(x)(env)) for x in shape_src.split("x"))
