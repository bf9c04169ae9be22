"""Turbo per-task dispatch: the static runtime's native fast path.

The reference's hot loop is µs-class generated C — select a ready task,
bind its copies, invoke the body hook, release successors
(parsec/scheduling.c:586-625 + the jdf2c-generated release_deps).  The
classic Python per-task path costs ~0.5 ms/task in interpreter glue
spread across dozens of small calls (scheduler queues, Task objects,
per-flow copy resolution, device-module bookkeeping), which no single
C helper can remove.  Turbo removes it structurally:

- data binding is PRECOMPILED: WaveRunner's slot assignment resolves
  every (task, flow) to a (pool, row) index pair at build time, so
  per-task binding is an index lookup, not a guard-evaluating walk;
- select -> release runs in C: ``NativeDAG.run_loop`` owns a priority
  max-heap over the lowered CSR counters and calls back into Python
  exactly ONCE per task — the chore invocation (one jitted XLA call on
  the task's slot rows);
- completion accounting is batched after the loop.

Semantics are the per-task runtime's, not wave's: tasks execute ONE AT
A TIME in any dependence-respecting priority order, and a task's
writes land in its slot in place — exactly the runtime's shared-copy
mutation model (a flow's body mutates the copy bound to it).  There is
no antichain batching and no gather-before-scatter wave semantics;
this is genuine per-task dispatch.

The honest floor (tools/turbo_profile.py prints the table): the
C select/release loop itself runs at reference scale (~0.3 us/task)
and the Python trampoline adds well under 1 us, but every task is
still ONE XLA executable submission, and that submission — even
AOT-pre-bound with donated buffers — costs on the order of 100 us
CPU-side.  Turbo's per-task cost is therefore the XLA dispatch floor,
one to two orders above the reference's ~1 us generated-C hook call,
and 5-10x below the classic dynamic-hash path.  Cutting further means
not dispatching per task at all — that is wave/capture's job, not
turbo's.

Writebacks are LAZY and device-resident: after the run, each written
tile's newest copy is a lazy slice of the device pool, materialized on
first read — a single-tile host read pulls exactly one tile D2H (the
round-1 lesson: never bulk-pull through a thin link).
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ...data.data import Coherency, DataCopy
from ...utils import logging as plog
from .wave import WaveError, WaveRunner

__all__ = ["TurboRunner", "LazyPoolCopy"]


class _PoolHolder:
    """The one strong owner of the result pools. Lazy copies reference
    THIS, never the runner: whatever outlives the run (the collection
    and its copies) keeps only the pools alive, not the runner's
    entries/plans/taskpool graph."""

    __slots__ = ("pools",)

    def __init__(self) -> None:
        self.pools: Tuple = ()


class LazyPoolCopy(DataCopy):
    """A device copy whose payload is a row of a stacked tile pool,
    sliced on first access: registering N tiles costs zero device
    dispatches, and a host read of one tile moves one tile."""

    __slots__ = ("_holder", "_pid", "_row", "_mat", "_val", "_armed")

    def __init__(self, data, device_id: int, holder, pid: int, row: int,
                 dtt=None) -> None:
        self._holder = holder
        self._pid = pid
        self._row = row
        self._mat = False
        self._val = None
        self._armed = False
        super().__init__(data, device_id, payload=None, dtt=dtt)
        self._armed = True

    @property
    def payload(self):
        if not self._mat:
            self._val = self._holder.pools[self._pid][self._row]
            self._mat = True
        return self._val

    @payload.setter
    def payload(self, v) -> None:
        if not self._armed:
            return      # DataCopy.__init__'s placeholder assignment
        self._mat = True
        self._val = v


class TurboRunner(WaveRunner):
    """Per-task executor over precompiled slot tables.

    Eligibility is WaveRunner's (slot assignment must resolve every
    flow); ineligible taskpools raise WaveError at construction and the
    caller falls back to the classic path.
    """

    def __init__(self, tp) -> None:
        super().__init__(tp, max_chunk=1)
        self._entries: Optional[List] = None
        self._holder = _PoolHolder()
        self._aug = self._augment_war_edges()

    @property
    def pools(self) -> Tuple:
        return self._holder.pools

    # ------------------------------------------------------------------ #
    def _augment_war_edges(self):
        """Anti-dependence (WAR) ordering, statically.

        Per-task in-place scatters mean a slot's next writer must wait
        for every reader of the CURRENT value — wave mode layers these
        inside each antichain (_split_war); turbo has no antichains, so
        the ordering becomes real edges: for each (slot, reader) pair,
        an edge reader -> next writer of that slot (by dependence
        level). Two same-level writers of one slot race and are
        rejected statically, like wave's two-writer check. Returns
        (indptr, succ, indegree) — the augmented CSR the run loop
        walks; cached on the DAG."""
        dag = self.dag
        cached = dag.kernel_cache.get("turbo_war")
        if cached is not None:
            return cached
        # dependence levels (longest path), Kahn order
        indeg = dag.indegree.copy()
        level = np.zeros(dag.n_tasks, np.int32)
        frontier = [int(t) for t in np.nonzero(indeg == 0)[0]]
        while frontier:
            nxt = []
            for t in frontier:
                for e in range(int(dag.indptr[t]), int(dag.indptr[t + 1])):
                    s = int(dag.succ[e])
                    level[s] = max(level[s], level[t] + 1)
                    indeg[s] -= 1
                    if indeg[s] == 0:
                        nxt.append(s)
            frontier = nxt
        writers: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        readers: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
        for t in range(dag.n_tasks):
            p = self.plans[int(dag.class_of[t])]
            lv = int(level[t])
            for k in range(len(p.flow_idx)):
                if p.written[k]:
                    for key in self._write_keys(t, p, k):
                        writers.setdefault(key, []).append((lv, t))
                if p.reads[k] or not p.written[k]:
                    key = (int(self._slot_coll[t, k]),
                           int(self._slot[t, k]))
                    readers.setdefault(key, []).append((lv, t))
        extra: List[Tuple[int, int]] = []
        for key, wl in writers.items():
            ws = sorted(set(wl))
            for a, b in zip(ws, ws[1:]):
                if a[0] == b[0] and a[1] != b[1]:
                    raise WaveError(
                        f"two unordered writers of one tile (tasks "
                        f"{a[1]} and {b[1]}): the DAG races — in-place "
                        f"per-task scatters would keep an arbitrary one")
                # write-after-write: successive writers execute in level
                # order even when no dataflow path orders them (wave
                # order; a redundant edge over an existing path is
                # harmless — it is walked like any other)
                extra.append((a[1], b[1]))
            for (lr, r) in readers.get(key, ()):
                for (lw, w) in ws:
                    if lw >= lr and w != r:
                        extra.append((r, w))   # reader before next writer
                        break
        if not extra:
            out = (dag.indptr, dag.succ, dag.indegree)
            dag.kernel_cache["turbo_war"] = out
            return out
        extra_by_src: Dict[int, List[int]] = {}
        indeg2 = dag.indegree.copy()
        for (r, w) in set(extra):
            extra_by_src.setdefault(r, []).append(w)
            indeg2[w] += 1
        indptr2 = np.zeros(dag.n_tasks + 1, np.int32)
        succ2: List[int] = []
        for t in range(dag.n_tasks):
            succ2.extend(int(dag.succ[e]) for e in
                         range(int(dag.indptr[t]), int(dag.indptr[t + 1])))
            succ2.extend(sorted(extra_by_src.get(t, ())))
            indptr2[t + 1] = len(succ2)
        succ2a = np.asarray(succ2, np.int32)
        # cyclic WAR (two tasks each reading the slot the other writes)
        # turns into a CYCLE here — per-task in-place scatters cannot
        # serve it; fail at build so the caller falls back to an engine
        # that can (fused wave gathers-before-scatter; the classic
        # runtime's copies)
        ind = np.array(indeg2, copy=True)
        frontier = [int(t) for t in np.nonzero(ind == 0)[0]]
        seen = 0
        while frontier:
            seen += len(frontier)
            nxt = []
            for t in frontier:
                for e in range(int(indptr2[t]), int(indptr2[t + 1])):
                    s = int(succ2a[e])
                    ind[s] -= 1
                    if ind[s] == 0:
                        nxt.append(s)
            frontier = nxt
        if seen != dag.n_tasks:
            raise WaveError(
                "cyclic write-after-read conflicts: per-task in-place "
                "scatters cannot serve this DAG — the classic runtime "
                "(copies) or fused wave (gather-before-scatter) can")
        out = (indptr2, succ2a, indeg2)
        dag.kernel_cache["turbo_war"] = out
        plog.debug.verbose(3, "turbo %s: %d WAR ordering edges added",
                           self.tp.name, len(set(extra)))
        return out

    # ------------------------------------------------------------------ #
    def _build_entries(self, pools, device=None) -> None:
        """Per-task (callable, arrays) entries: the index arrays staged
        as DEVICE constants once (a numpy arg would pay a host->device
        conversion per call), and the chunk kernel PRE-BOUND as an
        AOT-compiled executable per spec — the per-task cost is then
        pure submission, not signature matching / argument processing
        (round-4 VERDICT item 4; the reference's analog is the jdf2c-
        generated direct hook call, scheduling.c:586-625). Cached on
        the DAG — repeated taskpool instantiations with the same
        signature reuse them."""
        import jax

        dag = self.dag
        ck = ("turbo_entries", None if device is None else str(device))
        cached = dag.kernel_cache.get(ck)
        if cached is not None:
            self._entries = cached
            return
        entries = []
        compiled: Dict[Tuple, Any] = {}
        for t in range(dag.n_tasks):
            ids = np.asarray([t], np.int64)
            ent, _ = self._frontier_entries(ids, dag.class_of[ids], pools)
            spec, a = ent[0]
            put = (lambda x: jax.device_put(x, device)) \
                if device is not None else jax.device_put
            a = {k: put(v) for k, v in a.items()}
            fn = compiled.get(spec)
            if fn is None:
                fn = compiled[spec] = self._prebind(spec, pools, a)
            entries.append((fn, a))
        # ONE barrier for all staged index arrays: a per-entry sync
        # would pay one link round trip per task
        jax.block_until_ready([v for _fn, a in entries
                               for v in a.values()])
        if self._kernels_shareable:
            dag.kernel_cache[ck] = entries
        self._entries = entries

    def _prebind(self, spec: Tuple, pools, a) -> Any:
        """AOT-lower + compile the spec's chunk kernel against the run's
        concrete pool/index shapes (donation preserved from the jit
        wrapper). Falls back to the jitted callable when the AOT API is
        unavailable — semantics identical, dispatch a little heavier."""
        kern = self._kernel(*spec)
        try:
            return kern.lower(pools, a["locs"], a["idx_in"], a["idx_out"],
                              a["idx_wbx"]).compile()
        except Exception as exc:
            # body trace errors get the friendly wave diagnosis (the
            # trace runs inside lower() here, not at first call)
            werr = self._trace_error(exc, self.plans[spec[0]].ast.name)
            if werr is not None:
                raise werr from exc
            plog.debug.verbose(1, "turbo AOT prebind unavailable (%s); "
                               "using jit dispatch", exc)
            return kern

    def execute_per_task(self, pools, device=None) -> Tuple:
        """Run every task as ONE XLA call in C-driven priority order."""
        import time as _time

        if self._entries is None:
            self._build_entries(pools, device=device)
        holder = self._holder
        holder.pools = pools
        entries = self._entries

        def tramp(tid: int) -> None:
            fn, a = entries[tid]
            try:
                holder.pools = fn(holder.pools, a["locs"], a["idx_in"],
                                  a["idx_out"], a["idx_wbx"])
            except WaveError:
                raise
            except Exception as exc:
                # AOT-unavailable fallback: the body traces at FIRST
                # call, so trace errors surface here — give them the
                # same wave diagnosis _prebind gives AOT-path failures
                name = self.plans[int(self.dag.class_of[tid])].ast.name
                werr = self._trace_error(exc, name)
                if werr is not None:
                    raise werr from exc
                raise

        dag = self.dag
        indptr, succ, indeg = self._aug    # WAR/WAW-augmented CSR
        engine = self._make_aug_engine(indptr, succ, indeg)
        t0 = _time.perf_counter()
        prio = np.ascontiguousarray(dag.priority, np.int32)
        if engine is not None:
            done = int(engine.run_loop(tramp, prio))
        else:
            done = self._py_run_loop(tramp, prio, indptr, succ, indeg)
        if done != dag.n_tasks:
            raise WaveError(
                f"turbo execution stalled: {done}/{dag.n_tasks} tasks ran")
        self.stats = {
            "tasks": dag.n_tasks,
            "kernel_calls": dag.n_tasks,
            "dispatch_secs": round(_time.perf_counter() - t0, 6),
            "compiled_kernels": sum(len(p.kernels) for p in self.plans),
            "native_loop": engine is not None,
        }
        plog.debug.verbose(3, "turbo %s: %s", self.tp.name, self.stats)
        return self.pools

    @staticmethod
    def _make_aug_engine(indptr, succ, indeg):
        """A fresh NativeDAG over the augmented CSR (None -> use the
        Python loop). Flow arrays are zeros: the run loop routes no
        bindings (pools carry the data)."""
        try:
            from ...native import native as _native
            if _native is not None and hasattr(_native, "NativeDAG"):
                eng = _native.NativeDAG(
                    np.ascontiguousarray(indptr, np.int32),
                    np.ascontiguousarray(succ, np.int32),
                    np.zeros(len(succ), np.int8),
                    np.zeros(len(succ), np.int8),
                    np.ascontiguousarray(indeg, np.int32), 0)
                if hasattr(eng, "run_loop"):
                    return eng
        except Exception as exc:  # pragma: no cover - build-env dependent
            plog.debug.verbose(1, "native loop unavailable (%s)", exc)
        return None

    def _py_run_loop(self, tramp, prio, indptr, succ, indeg0) -> int:
        """Python mirror of NativeDAG.run_loop (extension unavailable)."""
        indeg = np.array(indeg0, copy=True)
        heap = [(-int(prio[t]), int(t))
                for t in np.nonzero(indeg == 0)[0]]
        heapq.heapify(heap)
        done = 0
        while heap:
            _, t = heapq.heappop(heap)
            tramp(t)
            for e in range(int(indptr[t]), int(indptr[t + 1])):
                s = int(succ[e])
                indeg[s] -= 1
                if indeg[s] == 0:
                    heapq.heappush(heap, (-int(prio[s]), s))
            done += 1
        return done

    # ------------------------------------------------------------------ #
    def attach_lazy_results(self, device_index: int) -> None:
        """Register every written tile's result as the newest DEVICE
        copy — a LazyPoolCopy slicing self.pools on first access. Host
        copies stay attached (stale); the coherency protocol pulls a
        tile D2H only when someone reads it."""
        holder = self._holder
        for pid, name in enumerate(self.pool_names):
            if pid not in self._written_colls:
                continue
            coll = self.collections[name]
            for row, c in enumerate(self._pool_coords[pid]):
                data = coll.data_of(*c)
                old = data.get_copy(device_index)
                if old is not None:
                    data._detach_copy(old)
                h0 = data.get_copy(0)
                lazy = LazyPoolCopy(data, device_index, holder, pid, row,
                                    dtt=None if h0 is None else h0.dtt)
                data.attach_copy(lazy)
                lazy.coherency = Coherency.OWNED
                data.version_bump(device_index)

    def run(self, device=None, device_index: Optional[int] = None) -> None:
        pools = self.execute_per_task(self.build_pools(device),
                                      device=device)
        if device_index is None:
            self.scatter_pools(pools)       # eager host writeback
        else:
            self.attach_lazy_results(device_index)
