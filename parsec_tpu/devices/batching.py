"""Batched device dispatch: stack same-class ready tasks into ONE jitted call.

The reference GPU module amortizes submission by pipelining stage-in /
exec / stage-out across streams (device_cuda_module.c, SURVEY §3.4); on
XLA the analogous lever is amortizing the *dispatch* itself — one
executable submission for a whole antichain of same-class tasks instead
of one per task (the batched-dispatch discipline of "Large Scale
Distributed Linear Algebra With TPUs", arxiv 2112.09017, and the
fine-grained compute/transfer overlap of T3, arxiv 2401.16677).

A task class opts in by attaching a :class:`DeviceBatchSpec` to its
device chore (``Chore.batch_spec``).  The spec separates the *per-task*
part (``extract``: which staged arrays are batchable and what static
context the body needs) from the *traceable* part (``call``: the body
as a pure function of those arrays).  The device module groups ready
tasks whose (spec, static context, shapes, dtypes) agree and dispatches
each group through one jitted callable built here.

A stacked program is UNROLLED: it contains one per-example subgraph
per task — N independent copies of exactly the graph the per-task path
traces, returned from ONE dispatch.  Results are bit-exact vs per-task
execution (each op lowers identically; measured for cholesky /
triangular-solve / matmul on the CPU backend, where a vmapped body is
NOT bit-exact for triangular solve: XLA picks a different batched
algorithm), at the cost of program size growing with the bucket.

Batch sizes are bucketed to powers of two so the set of programs stays
small: 2, 4, 8, ... up to ``device_batch_max`` (16); a bucket is ONE
call, on one rank and across ranks.  A program is keyed by (bucket,
static, shapes/dtypes, donate mask) and built ONCE PER PROCESS for
every spec that can say what
its ``call`` traces to (``cache_token``): a DTD kernel's token is the
user function, a PTG body's is made from what the body reads
(dsl/ptg/body_token.py) at its first stacked dispatch.  A fresh
taskpool over the same bodies and tile shapes then dispatches programs
an earlier one traced, lowered and loaded (``program_reuse`` in
``dev.stats``).  Only a spec with no token -- a body reading a
collection, a prologue helper, ``eval`` -- keeps its programs on itself,
where they die with its taskpool.

Mesh-sharded stacking (ISSUE 6): when the rank's device is a chip MESH
(``device_mesh_shape``), a flush group whose size divides the chip
count compiles through ``shard_map`` over the mesh instead — the
stacked batch axis is sharded across the chips, each chip runs its
local slice of per-example subgraphs, and ONE jitted call executes the
whole group spread over the mesh (the distribute-then-collect shape of
arxiv 2112.09017).  Inputs arrive as one global array per batch arg
(assembled chip-locally by the device module), so intra-mesh data
movement is XLA's job, not the wire's.
"""
from __future__ import annotations

import re
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Set, Tuple

__all__ = ["DeviceBatchSpec", "bucket_size",
           "stacked_callable_key", "program_name", "KernelsNamedFor",
           "kernel_named_for", "programs_held",
           "settle", "downgrade",
           "build_stacked_callable", "cached_stacked_callable",
           "build_sharded_callable", "cached_sharded_callable",
           "cached_stage_callable"]


class DeviceBatchSpec:
    """Recipe for stacking same-class tasks into one jitted dispatch.

    ``extract(task, arrays) -> None | (bargs, flow_idx, static)``
        Per-task, non-traced.  ``arrays`` is the device module's staged
        per-flow array list.  Returns the batchable array args (all jax
        arrays), the flow index behind each (for access/donation
        decisions), and a hashable static key covering EVERYTHING the
        body reads that is not a batched array (referenced locals,
        VALUE params, absent-flow mask, ...).  ``None`` means this task
        cannot batch (falls back to per-task ``dyld_fn`` dispatch).

    ``call(bargs, static) -> tuple`` — the body as a traceable pure
        function: per-task outputs for the written flows, in flow
        order.  Invoked under jit, so it must be jax-traceable; an untraceable body is detected at
        the first batched dispatch and the spec permanently falls back
        (``batchable = False``).

    ``cache_token`` (optional): a hashable naming, by value, everything
    ``call`` traces to, with ``call`` reading nothing else (the DTD
    user kernel: ``call`` reassembles its args from the static key and
    calls only that function).  Two specs with equal tokens share their
    programs process-wide, so a NEW taskpool over the same kernel and
    shapes neither traces, lowers nor loads; a trace failure is
    remembered with the token too.  With no token the programs cache on
    the spec and die with it.

    ``late_token`` (optional): ``() -> None | (cache_token, call)`` for
    a spec that can only say what it reads once its taskpool is set up
    (a PTG body: the taskpool's globals are final at the first task,
    not when the class is built).  Asked once, at the spec's first
    stacked dispatch (:func:`settle`); an answer replaces ``call`` with
    one built from the token alone, so the shared program holds no
    taskpool.

    ``ahead`` (0 unless the operation that built the taskpool sets it):
    the most tasks of the class one device can be handed at once, where
    the operation knows it (``ops.dgetrf_1d`` over ``g`` accelerators:
    a chip's share of the block columns).  A device then builds the
    class's programs at its first group of the class, all of them: the
    lone task's and every stacked bucket up to that many
    (``JaxDevice._build_ahead``), so which buckets the arrival of its
    ready sets happens to form in a later call builds nothing there.
    """

    __slots__ = ("name", "extract", "call", "batchable", "cache",
                 "cache_token", "late_token", "mesh_ok", "ahead",
                 "__weakref__")

    def __init__(self, name: str,
                 extract: Callable[[Any, Any], Optional[Tuple]],
                 call: Callable[[Tuple, Any], Tuple],
                 cache_token: Any = None,
                 late_token: Optional[Callable[[], Optional[Tuple]]] = None
                 ) -> None:
        self.name = name
        self.extract = extract
        self.call = call
        self.batchable = True   # cleared on first trace failure
        self.cache: Dict[Any, Any] = {}   # programs of a spec with no token
        self.cache_token = cache_token
        self.late_token = late_token
        # cleared when the mesh-sharded stacking of THIS class fails to
        # trace/dispatch (the single-chip stacked path stays available)
        self.mesh_ok = True
        self.ahead = 0


def bucket_size(navail: int, batch_max: int) -> int:
    """Largest power-of-two <= min(navail, batch_max): bounded compile
    set {2, 4, 8, ...} while still amortizing most of a burst."""
    n = min(navail, max(2, batch_max))
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


def program_name(spec_name: str, n: int) -> str:
    """The name a dispatched program carries in a device trace
    (``XLA Modules`` reads ``jit_<name>``): ``<CLASS>_x<n>`` for a
    stacked or sharded batch of ``n`` tasks, ``<CLASS>`` for the
    kernels one task's body calls.  From the class name and the bucket
    alone — no ids — so the persistent compile cache hits from taskpool
    to taskpool and from process to process."""
    cls = re.sub(r"\W", "_", spec_name.split("[", 1)[0]) or "task"
    return cls if n == 1 else f"{cls}_x{n}"


def _named(fn: Callable, name: str) -> Callable:
    fn.__name__ = fn.__qualname__ = name
    return fn


#: (class name, jitted kernel) -> the kernel's clone named for the class
_class_kernels: Dict[Tuple[str, Any], Any] = {}


def kernel_named_for(name: str, obj: Any) -> Any:
    """``obj`` if it is not a plainly jitted kernel (``ops.potrf``, ...),
    else its clone that runs as ``jit_<name>``.  The clones are built
    once per process per (name, kernel), as the kernels themselves
    are."""
    info = getattr(obj, "_jit_info", None)     # jax 0.9.0 PjitFunction
    if info is None or not hasattr(obj, "__wrapped__"):
        return obj
    clone = _class_kernels.get((name, obj))
    if clone is None:
        import functools

        import jax
        fun = obj.__wrapped__

        @functools.wraps(fun)
        def kernel(*args, **kwargs):
            return fun(*args, **kwargs)

        clone = _class_kernels[(name, obj)] = jax.jit(
            _named(kernel, name),
            static_argnums=info.static_argnums,
            static_argnames=info.static_argnames,
            donate_argnums=info.donate_argnums,
            donate_argnames=info.donate_argnames)
    return clone


def kernel_with_constants(name: str, obj: Any, template: Tuple) -> Any:
    """The clone of the plainly jitted kernel ``obj`` that runs as
    ``jit_<name>`` with the arguments ``template`` marks ``("v", value)``
    bound as constants and its ``None`` entries taken as the call's
    arrays, in order: what a stacked program makes of a DTD task's
    VALUE parameters (``DeviceBatchSpec.call`` with its static key), for
    a task dispatched alone, so that a task's result does not depend on
    how many tasks its call held.  None where ``obj`` is not plainly
    jitted or marks arguments of its own static or donated.  Built once
    per process per (name, kernel, constants)."""
    info = getattr(obj, "_jit_info", None)
    if info is None or not hasattr(obj, "__wrapped__") \
            or info.static_argnums or info.static_argnames \
            or info.donate_argnums or info.donate_argnames:
        return None
    key = (name, (obj, template))
    clone = _class_kernels.get(key)
    if clone is None:
        import jax
        fun = obj.__wrapped__

        def kernel(*arrays):
            it = iter(arrays)
            return fun(*[next(it) if s is None else s[1] for s in template])

        clone = _class_kernels[key] = jax.jit(_named(kernel, name))
    return clone


class KernelsNamedFor:
    """A module as a per-task device body sees it: every plainly jitted
    kernel it holds (``ops.potrf``, ...) comes back as a clone named for
    the body's task class (:func:`kernel_named_for`), so a task
    dispatched alone runs ``jit_<CLASS>`` and not ``jit_potrf``;
    anything else the module holds passes through."""

    __slots__ = ("_module", "_name")

    def __init__(self, module: Any, cls: str) -> None:
        self._module = module
        self._name = program_name(cls, 1)

    def __getattr__(self, attr: str) -> Any:
        return kernel_named_for(self._name, getattr(self._module, attr))


def stacked_callable_key(n: int, nargs: int, static: Any,
                         shapes: Tuple, donate: Tuple) -> Tuple:
    return (n, nargs, static, shapes, donate)


#: process-wide stacked-callable cache for specs with a ``cache_token``
#: (taskpool-independent bodies): token -> key -> jitted callable
_shared_cache: Dict[Any, Dict[Any, Any]] = {}

#: tokens whose ``call`` failed to trace: every later spec of the token
#: is downgraded without tracing again
_untraceable: Set[Any] = set()

#: serializes settling a spec and building a program: the managers of
#: several devices dispatch one taskpool's specs concurrently, and each
#: program is to be built (and each downgrade counted) once
_lock = threading.Lock()


def settle(spec: DeviceBatchSpec) -> bool:
    """Ask a spec's ``late_token``, once, at its first stacked dispatch.
    False when that downgraded the spec: an earlier spec of the same
    token failed to trace, and this one goes per-task without trying."""
    with _lock:
        late = spec.late_token
        if late is None:
            return True     # another device's manager settled it
        named = late()
        if named is not None:
            spec.cache_token, spec.call = named
        known_bad = spec.cache_token in _untraceable
        if known_bad:
            spec.batchable = False
        spec.late_token = None      # last: readers take no lock
        return not known_bad


def downgrade(spec: DeviceBatchSpec) -> None:
    """``spec.call`` failed to trace or dispatch stacked: the spec, and
    every later spec of its token, dispatch per-task from here on."""
    spec.batchable = False
    spec.cache.clear()
    if spec.cache_token is not None:
        _untraceable.add(spec.cache_token)
        _shared_cache.pop(spec.cache_token, None)


def _cached(spec: DeviceBatchSpec, key: Tuple,
            build: Callable[[], "_Program"]) -> "_Program":
    cache = (_shared_cache.setdefault(spec.cache_token, {})
             if spec.cache_token is not None else spec.cache)
    fn = cache.get(key)
    if fn is None:
        with _lock:
            fn = cache.get(key)
            if fn is None:
                fn = cache[key] = build()
    return fn


def programs_held(classes: Optional[Set[str]] = None) -> int:
    """Compiled programs the process holds for task classes, by the
    names they dispatch under (:func:`program_name`): every signature
    of every token-cached stacked program (``<CLASS>_x<n>``) and of
    every kernel cloned for a lone task (``<CLASS>``).  ``classes``
    restricts the count to those classes.  A level, not a counter: it
    stands still once every shape has run, and a body whose program
    depends on a task local (one signature per ``k``) shows as a count
    that grows with the DAG."""
    def mine(name: str) -> bool:
        return classes is None or re.sub(r"_x\d+$", "", name) in classes

    with _lock:
        stacked = [fn for cache in _shared_cache.values()
                   for fn in cache.values() if mine(fn.name)]
    lone = [fn for (name, _), fn in list(_class_kernels.items())
            if mine(name)]
    return (sum(fn.fn._cache_size() for fn in stacked)
            + sum(fn._cache_size() for fn in lone))


#: process-wide stage-callable cache (stagec/, ISSUE 12), living
#: alongside the bucket cache above: token -> key -> fused jitted
#: callable (or the stagec failure sentinel).  The token embeds the
#: parsed spec object + scalar globals + collection geometry, so a
#: fresh taskpool over the same (spec, NB, dtype) hits already-traced
#: stages — the PTG analog of the DTD ``cache_token`` steady state.
_stage_cache: Dict[Any, Dict[Any, Any]] = {}


def cached_stage_callable(token: Any, key: Any, build: Callable) -> Any:
    """Fetch-or-build one stage's lowered callable.  ``build`` runs at
    most once per (token, key); whatever it returns (including a
    failure sentinel recorded by the stage compiler) is returned to
    every later caller."""
    cache = _stage_cache.setdefault(token, {})
    fn = cache.get(key)
    if fn is None:
        fn = build()
        cache[key] = fn
    return fn


def cached_stacked_callable(spec: DeviceBatchSpec, n: int, nargs: int,
                            static: Any, shapes: Tuple,
                            donate: Tuple[bool, ...] = ()) -> Callable:
    """The AOT-cached stacked callable for this signature: per-token
    process-wide when the spec has a token (a new taskpool over the
    same kernel/shapes skips tracing, lowering AND loading), else
    per-spec (dies with the taskpool)."""
    return _cached(
        spec, stacked_callable_key(n, nargs, static, shapes, donate),
        lambda: build_stacked_callable(spec, n, nargs, static, donate))


def build_stacked_callable(spec: DeviceBatchSpec, n: int, nargs: int,
                           static: Any,
                           donate: Tuple[bool, ...] = ()) -> Callable:
    """One jitted callable executing ``n`` same-signature tasks, as
    per-example subgraphs: bit-exact vs per-task dispatch.

    Flat calling convention (grouped by arg so donation maps to whole
    arg groups): ``flat[j * n + i]`` is batch-arg ``j`` of task ``i``;
    the result is flat grouped by output: ``out[k * n + i]`` is output
    ``k`` of task ``i``.

    The closure captures ``spec.call`` only (never the spec), so a
    token-cached callable shared across taskpools keeps just the
    underlying kernel alive.
    """
    import jax
    call = spec.call

    def stacked(*flat):
        rows = [call(tuple(flat[j * n + i] for j in range(nargs)), static)
                for i in range(n)]
        n_out = len(rows[0])
        return tuple(rows[i][k] for k in range(n_out)
                     for i in range(n))

    donate_argnums = tuple(j * n + i for j, d in enumerate(donate) if d
                           for i in range(n))
    name = program_name(spec.name, n)
    return _Program(jax.jit(_named(stacked, name),
                            donate_argnums=donate_argnums), name, spec)


def cached_sharded_callable(spec: DeviceBatchSpec, n: int, nargs: int,
                            static: Any, shapes: Tuple,
                            mesh: Any) -> Callable:
    """The AOT-cached mesh-sharded stacked callable for this signature.
    The Mesh OBJECT joins the key (jax meshes hash by devices + axis
    names): the key holds a strong reference, so a recycled id can
    never alias a dead mesh's entry, a different mesh (another rank's
    device in the same process) compiles its own entry, and a fresh
    context rebuilding the SAME mesh over the same chips hits the
    token-cached callable."""
    return _cached(
        spec, ("mesh", mesh, n, nargs, static, shapes),
        lambda: build_sharded_callable(spec, n, nargs, static, shapes,
                                       mesh))


def build_sharded_callable(spec: DeviceBatchSpec, n: int, nargs: int,
                           static: Any, shapes: Tuple,
                           mesh: Any) -> Callable:
    """One jitted shard_map call executing ``n`` same-signature tasks
    SPREAD ACROSS the chip mesh.

    Calling convention: one GLOBAL array per batch arg, shape
    ``(n,) + row_shape``, sharded over every mesh axis on the leading
    (batch) dim — chip ``c`` holds rows ``[c*n/k, (c+1)*n/k)``.  Each
    chip's shard_map body runs its local rows as one per-example
    subgraph per local row (bit-exact vs the single-chip stacked path:
    the SAME per-example graph lowers on one chip either way).
    Outputs come back as global arrays with the same leading-axis
    sharding; the device module slices per-task rows from the
    addressable shards so results stay chip-resident.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import shard_map_compat

    call = spec.call
    k = int(mesh.devices.size)
    assert n % k == 0, (n, k)
    n_local = n // k
    axes = tuple(mesh.axis_names)
    batch_spec = PartitionSpec(axes)   # leading dim over ALL mesh axes
    # output arity from an abstract trace of one example (shapes are
    # the group key, so this is exact for every task in the group)
    row_avals = tuple(jax.ShapeDtypeStruct(s, d) for (s, d) in shapes)
    out_avals = jax.eval_shape(lambda *r: call(r, static), *row_avals)
    n_out = len(out_avals)

    def local_fn(*blocks):
        rows = [call(tuple(b[i] for b in blocks), static)
                for i in range(n_local)]
        return tuple(jnp.stack([rows[i][o] for i in range(n_local)])
                     for o in range(n_out))

    sharded = shard_map_compat(local_fn, mesh,
                            in_specs=(batch_spec,) * nargs,
                            out_specs=(batch_spec,) * n_out)
    in_sh = NamedSharding(mesh, batch_spec)
    name = program_name(spec.name, n)

    def program(*gargs):
        return sharded(*gargs)

    fn = jax.jit(_named(program, name), in_shardings=(in_sh,) * nargs,
                 out_shardings=(in_sh,) * n_out)
    return _Program(fn, name, spec, n_out, in_sh)


class _Program:
    """A jitted dispatch plus what the device module needs to know about
    it (jit objects reject attribute assignment, hence the wrapper): its
    trace name, whether a device has called it yet (the first call
    traces, lowers and loads: ``first_call_ns``; once per device per
    process for a program cached by token), which spec built it, and
    for a shard_map dispatch the metadata to assemble inputs / slice
    outputs."""

    __slots__ = ("fn", "name", "n_out", "sharding", "_called_on",
                 "_builder")

    def __init__(self, fn: Callable, name: str, spec: DeviceBatchSpec,
                 n_out: int = 0, sharding: Any = None) -> None:
        self.fn = fn
        self.name = name
        self.n_out = n_out
        self.sharding = sharding
        self._called_on: set = set()
        self._builder = weakref.ref(spec)   # never the spec: it holds
        # its taskpool

    def __call__(self, *args):
        return self.fn(*args)

    def reused_by(self, spec: DeviceBatchSpec) -> bool:
        """True when another spec (an earlier taskpool's) built this
        program: ``program_reuse``."""
        return self._builder() is not spec

    def first_call_on(self, device: str) -> bool:
        """True the first time the device named ``device`` asks: each
        device's first call loads the program onto its own chip(s)."""
        if device in self._called_on:
            return False
        self._called_on.add(device)
        return True
