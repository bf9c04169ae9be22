"""Sharded stage variants: compile a wave-front stage through
``parallel/mesh.py::shard_map_compat`` over the rank's chip mesh
(ISSUE 12 tentpole, part 3).

When the rank's accelerator is a chip MESH (``device_mesh_shape``,
PR 6) the planner emits per-(level, class) wave-front stages and this
module lowers the eligible ones as ONE shard_map-compiled jitted call
spanning every chip: the member axis is sharded over the mesh, each
chip runs its block of per-example subgraphs in ``unroll`` style (the
same bit-exactness argument as ``devices/batching.build_sharded_callable``
— identical per-example graphs, one chip or many).

Eligibility (checked here, not at plan time — it needs concrete
shapes): single class, every member flow bound to its own exclusive
packed slot (no shared tiles, no NEW/NULL bindings), and a member
count divisible by the chip count.  A body that reads declared LOCALS
no longer rejects (ISSUE 13 STG relaxation): the referenced locals'
per-member values ride an extra ``(n, L)`` int32 argument sharded
over the member axis, and each row's body sees them as TRACED scalars
— so e.g. a wave whose body scales by ``k`` still compiles as one
shard_map call.  A body that uses a local in Python control flow
fails the forced trace and falls back like any other trace failure.
Ineligible stages — and any failure while assembling or tracing the
sharded call — fall back to the fused single-chip callable
transparently.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["wavefront_info", "build_wavefront_callable",
           "dispatch_sharded"]


class WavefrontInfo:
    """Per-stage metadata for the sharded dispatch: which packed slot
    feeds each (member, flow) and where each output row lands."""

    __slots__ = ("class_name", "flow_names", "arg_slots", "code",
                 "rep_env", "out_mem_map", "edge_map", "n", "nargs",
                 "local_names", "local_vals")

    def __init__(self, class_name: str, flow_names: List[str],
                 arg_slots: List[List[int]], code: Any, rep_env: Dict,
                 out_mem_map: List[Tuple[int, int]],
                 edge_map: List[Tuple[int, int]],
                 local_names: Tuple[str, ...] = (),
                 local_vals: Optional[List[Tuple[int, ...]]] = None) -> None:
        self.class_name = class_name
        self.flow_names = flow_names
        self.arg_slots = arg_slots        # [member][flow] -> slot index
        self.code = code
        self.rep_env = rep_env
        #: layout.out_mem order -> (member index, flow index)
        self.out_mem_map = out_mem_map
        #: layout.edge_outs order -> (member index, flow index)
        self.edge_map = edge_map
        self.n = len(arg_slots)
        self.nargs = len(flow_names)
        #: locals the body READS (co_names ∩ declared locals): their
        #: per-member values ship as one (n, L) int32 traced argument
        self.local_names = local_names
        self.local_vals = local_vals or []


def wavefront_info(tp, stage, layout, codes) -> Optional[WavefrontInfo]:
    """Analyze a stage for sharded eligibility; None = fused path."""
    members = stage.members
    if not members:
        return None
    cls = members[0].tc.ast.name
    if any(m.tc.ast.name != cls for m in members):
        return None
    tc_ast = members[0].tc.ast
    code = codes[cls]
    names = set(code.co_names)
    # a body reading locals shards anyway (ISSUE 13): the referenced
    # locals become per-row traced scalars instead of rejecting
    local_names = tuple(ld.name for ld in tc_ast.locals
                        if ld.name in names)
    local_vals: List[Tuple[int, ...]] = []
    if local_names:
        try:
            local_vals = [
                tuple(int(m.env[nm]) for nm in local_names)
                for m in members]
        except (KeyError, TypeError, ValueError):
            return None   # non-integer local: not shippable as scalars
    nonctl = [f for f in tc_ast.flows if not f.is_ctl]
    from .lower import _producer_locals
    class_ast = {tc.ast.name: tc.ast for tc in tp.task_classes}
    mkeys = stage.member_keys
    arg_slots: List[List[int]] = []
    used = set()
    for i, inst in enumerate(members):
        row: List[int] = []
        for f in nonctl:
            slot = None
            for d in f.deps_in():
                t = d.resolve(inst.env)
                if t is None:
                    continue
                if t.kind == "task":
                    pk = (t.task_class, _producer_locals(
                        class_ast, t.task_class,
                        tuple(a(inst.env) for a in t.args)))
                    if pk in mkeys:
                        return None   # intra-stage edge: not a wave front
                    slot = layout.slot_of_act(inst.key, f.name)
                elif t.kind == "memory":
                    coords = tuple(int(a(inst.env)) for a in t.args)
                    slot = layout.mem_index.get((t.collection, coords))
                break
            if slot is None and not f.deps_in():
                for d in f.deps_out():
                    t = d.resolve(inst.env)
                    if t is not None and t.kind == "memory":
                        coords = tuple(int(a(inst.env)) for a in t.args)
                        slot = layout.mem_index.get((t.collection, coords))
                        break
            if slot is None or slot in used:
                return None   # NEW/NULL binding or a shared tile
            used.add(slot)
            row.append(slot)
        arg_slots.append(row)

    # output row mapping: which (member, flow) produced each written
    # tile and each edge live-out
    flow_pos = {f.name: j for j, f in enumerate(nonctl)}
    writer: Dict[Tuple, Tuple[int, int]] = {}
    for i, inst in enumerate(members):
        for f in nonctl:
            if f.access not in ("RW", "WRITE"):
                continue
            for d in f.deps_out():
                t = d.resolve(inst.env)
                if t is None or t.kind != "memory":
                    continue
                coords = tuple(int(a(inst.env)) for a in t.args)
                writer[(t.collection, coords)] = (i, flow_pos[f.name])
    out_mem_map: List[Tuple[int, int]] = []
    for si in layout.out_mem:
        key = layout.mem_slots[si][0]
        if key not in writer:
            return None
        out_mem_map.append(writer[key])
    mindex = {m.key: i for i, m in enumerate(members)}
    edge_map = [(mindex[mk], flow_pos[fn])
                for (mk, fn) in layout.edge_outs]
    return WavefrontInfo(cls, [f.name for f in nonctl], arg_slots, code,
                         dict(members[0].env), out_mem_map, edge_map,
                         local_names, local_vals)


def build_wavefront_callable(mesh, info: WavefrontInfo, rank: int,
                             shapes: Tuple):
    """ONE shard_map-compiled jitted call running the wave front spread
    across ``mesh``: global inputs sharded over the member axis, each
    chip unrolling its local rows.  Returns ``(fn, sharding)`` where
    ``fn(*global_args) -> per-flow global arrays`` (post-body value of
    every flow, stacked member-major)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import shard_map_compat

    k = int(mesh.devices.size)
    n, nargs = info.n, info.nargs
    assert n % k == 0, (n, k)
    per = n // k
    axes = tuple(mesh.axis_names)
    batch = PartitionSpec(axes)
    code, rep_env, flow_names = info.code, info.rep_env, info.flow_names
    local_names = info.local_names
    n_in = nargs + (1 if local_names else 0)

    def local_fn(*blocks):
        rows = []
        for r in range(per):
            env = dict(rep_env)
            for j, fname in enumerate(flow_names):
                env[fname] = blocks[j][r]
            # per-row locals as traced scalars (ISSUE 13 relaxation):
            # blocks[nargs] is this chip's (per, L) slice of the
            # member-major locals array
            for li, nm in enumerate(local_names):
                env[nm] = blocks[nargs][r, li]
            env["np"] = np
            env["jnp"] = jnp
            env["es_rank"] = rank
            env["this_task"] = None
            exec(code, env)
            rows.append(tuple(env.get(fname) for fname in flow_names))
        return tuple(jnp.stack([rows[r][o] for r in range(per)])
                     for o in range(len(flow_names)))

    sharded = shard_map_compat(local_fn, mesh,
                            in_specs=(batch,) * n_in,
                            out_specs=(batch,) * len(flow_names))
    sh = NamedSharding(mesh, batch)
    fn = jax.jit(sharded, in_shardings=(sh,) * n_in,
                 out_shardings=(sh,) * len(flow_names))
    # force the trace NOW so eligibility failures downgrade at build
    # time, not mid-dispatch
    avals = [jax.ShapeDtypeStruct((n,) + s, d) for (s, d) in shapes]
    if local_names:
        avals.append(jax.ShapeDtypeStruct((n, len(local_names)),
                                          np.int32))
    fn.lower(*avals)
    return fn, sh


def dispatch_sharded(device, fn, sharding, info: WavefrontInfo,
                     arrays: List[Any]) -> Tuple[List[Any], List[Any]]:
    """Assemble the global member-major inputs, run the sharded call,
    and slice per-row outputs back out.  Returns ``(tile_outs,
    edge_outs)`` in layout order.  Anything raised here is caught by
    the caller and downgrades the stage to the fused callable."""
    import jax
    import jax.numpy as jnp

    mesh = device.mesh
    chips = list(device.chips)
    k = len(chips)
    n, nargs = info.n, info.nargs
    per = n // k
    blocks = []   # blocks[c][j]: chip c's shard of arg j
    for c, chip in enumerate(chips):
        per_arg = []
        for j in range(nargs):
            rows = [jax.device_put(arrays[info.arg_slots[c * per + r][j]],
                                   chip)
                    for r in range(per)]
            per_arg.append(jnp.stack(rows))
        blocks.append(per_arg)
    shapes = [tuple(arrays[info.arg_slots[0][j]].shape)
              for j in range(nargs)]
    gargs = [jax.make_array_from_single_device_arrays(
        (n,) + shapes[j], sharding, [blocks[c][j] for c in range(k)])
        for j in range(nargs)]
    if info.local_names:
        # member-major locals array, one (per, L) int32 shard per chip
        loc = np.asarray(info.local_vals, dtype=np.int32)
        loc_shards = [jax.device_put(loc[c * per:(c + 1) * per], chip)
                      for c, chip in enumerate(chips)]
        gargs.append(jax.make_array_from_single_device_arrays(
            loc.shape, sharding, loc_shards))
    outs = fn(*gargs)
    pos = {d: i for i, d in enumerate(chips)}
    shards = [sorted(o.addressable_shards, key=lambda s: pos[s.device])
              for o in outs]

    def row(i: int, o: int):
        c, r = divmod(i, per)
        return shards[o][c].data[r]

    tile_outs = [row(i, o) for (i, o) in info.out_mem_map]
    edge_outs = [row(i, o) for (i, o) in info.edge_map]
    return tile_outs, edge_outs
