#!/usr/bin/env python3
"""On the chip, by hand: what staging a wide front of host tiles costs
when the manager copies the WHOLE front in one list ``device_put`` and
only then dispatches the kernels that read it, against copying it chunk
by chunk with each chunk's kernels dispatched right behind its copy.

    python3 perfbench/checks/stage_front_probe.py [--fronts ...] [--bounds 32,64,128] [--reps 3]

One process, one thread, no runtime: ``jax.device_put`` and the tile
kernels of ``parsec_tpu.ops.linalg`` alone, stacked as the device module
stacks them (one jitted program of n subgraphs for a bucket of n = 16,
8, 4, 2 tasks, the kernel itself for a lone task; every task hands over
operands of its own).  A front is n host tiles (numpy, f32) of one
shape, each the written operand of one task whose other operands are on
the chip already:

- ``gemm2048``: 120 tiles of (2048, 2048) = 16.8 MB under ``gemm_nt``:
  the trailing tiles of ``ops.dpotrf`` at NT = 16 after TRSM(0);
- ``update1024``: 31 block columns of (32768, 1024) = 134 MB under
  ``getrf_1d_update`` at first row 0 of a panel ``getrf_1d_panel``
  really factored: ``ops.dgetrf_1d`` at NT = 32 after PANEL(0);
- ``gemm512``: 528 tiles of (512, 512) = 1 MB under ``gemm_nt``: the
  whole lower triangle of ``ops.dpotrf`` at NT = 32.

Of each front, the median over ``--reps`` of the host's clock from the
first ``device_put`` to ``block_until_ready`` on every kernel's result
(``wall_ms``), and of the part of it the calling thread spent before its
last dispatch returned (``thread_ms``; of it inside ``device_put``:
``put_ms``):

- ``whole``: ONE list put of the n tiles, then the calls;
- ``chunk<B>``: tiles taken in order, a chunk closed where its bytes
  reach B MiB; ONE list put a chunk, its calls right behind it;
  ``chunk<B>_landed``: before a put the thread waits until the put
  before it has landed (``block_until_ready`` on its tiles: one copy in
  flight); ``chunk<B>_behind``: until the put before THAT one has (two
  in flight);
- ``put_only`` (the one list put, to ``block_until_ready`` on the
  tiles; ``return_ms``: when ``device_put`` handed the thread back) and
  ``calls_only`` (the tiles on the chip already): what each half costs
  alone.  ``whole`` near their sum says nothing of the copy is hidden;
  ``chunk<B>`` near the larger says the chip computes WHILE a
  host-to-chip copy is in flight;
- ``landing_ms``: the puts of the middle bound issued back to back with
  no call between: when every eighth was issued and when it had landed
  (waited for only after the last was issued: a put that landed before
  then reads that time);
- ``together``: the calls dispatched on resident tiles FIRST, then the
  one list put of the same bytes, both waited for: the same question
  asked without any chunking.

ISSUE 44's rule, written before the run: a chunked pass goes into
``devices/tpu.py`` only if some bound of 32 / 64 / 128 MiB reads at
least 25% shorter than ``whole`` at ``gemm2048`` AND ``update1024`` and
no more than 3% longer at ``gemm512``.

Prints one JSON object last and writes it to
``chiprun_out/stage_front_probe.json``.  Refuses to run without a TPU: a
time here is a chip time (``--rehearse``: tiny fronts for the CPU, no
rule).  Never run by the benchmark's own runs.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STACK = 16
FRONTS = {   # name -> (kernel, tiles, tile shape)
    "gemm2048": ("gemm", 120, (2048, 2048)),
    "update1024": ("update", 31, (32768, 1024)),
    "gemm512": ("gemm", 528, (512, 512)),
}
REHEARSAL = {
    "gemm2048": ("gemm", 12, (64, 64)),
    "update1024": ("update", 5, (256, 32)),
    "gemm512": ("gemm", 40, (32, 32)),
}


def chunks_of(nbytes, bound):
    """Tile indices in order, a chunk closed where its bytes reach
    ``bound`` (None: one chunk)."""
    out, cur, held = [], [], 0
    for i, b in enumerate(nbytes):
        cur.append(i)
        held += b
        if bound is not None and held >= bound:
            out.append(cur)
            cur, held = [], 0
    if cur:
        out.append(cur)
    return out


def buckets_of(n):
    """The device module's buckets for a group of n tasks."""
    from parsec_tpu.devices.batching import bucket_size
    out = []
    while n >= 2:
        b = bucket_size(n, STACK)
        out.append(b)
        n -= b
    return out + [1] * n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fronts", default=",".join(FRONTS))
    ap.add_argument("--bounds", default="32,64,128",
                    help="chunk bounds in MiB")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"stage_front_probe: needs a TPU, found {dev.platform}")
    jax.config.update("jax_default_matmul_precision", "highest")
    from parsec_tpu.ops import linalg
    bounds = [int(b) for b in args.bounds.split(",")]
    fronts = REHEARSAL if args.rehearse else FRONTS
    mib = 1 << (10 if args.rehearse else 20)   # rehearsal: bounds in KiB

    def stacked(kernel, b, nargs):
        if b == 1:
            return kernel
        def program(*flat):
            cols = [flat[j * b:(j + 1) * b] for j in range(nargs)]
            return tuple(kernel(*a) for a in zip(*cols))
        program.__name__ = f"{kernel.__name__}_x{b}"
        return jax.jit(program)

    def ready(x):
        jax.block_until_ready(x)
        return time.perf_counter()

    report = {"device": dev.device_kind, "reps": args.reps, "stack": STACK,
              "bounds_mib": bounds, "fronts": {}}
    for name in args.fronts.split(","):
        kind, n, shape = fronts[name]
        rng = np.random.default_rng(2 ** 31 + 44 + n)
        tiles = [rng.standard_normal(shape).astype(np.float32)
                 for _ in range(n)]
        nbytes = [t.nbytes for t in tiles]
        if kind == "gemm":
            kernel, host_at = linalg.gemm_nt, 0
            others = [tuple(jnp.asarray(rng.standard_normal(shape).astype(
                np.float32)) for _ in range(2)) for _ in range(STACK)]
        else:
            kernel, host_at = linalg.getrf_1d_update, 2
            q = np.zeros((linalg.PIV_ROWS, shape[0]), np.int32)
            q[2] = np.arange(shape[0])
            others = [linalg.getrf_1d_panel(jnp.asarray(
                rng.standard_normal(shape).astype(np.float32)),
                jnp.asarray(q))]
        jax.block_until_ready(others)
        programs = {b: stacked(kernel, b, 3) for b in (16, 8, 4, 2, 1)}

        def calls(on_chip, at):
            """Dispatch the tasks of the tiles ``on_chip`` (a front's
            tiles ``at`` ...), bucket by bucket; the results."""
            outs, i = [], 0
            for b in buckets_of(len(on_chip)):
                part = tuple(on_chip[i:i + b])
                rest = [others[(at + i + j) % len(others)] for j in range(b)]
                cols = [tuple(r[k] for r in rest) for k in range(2)]
                cols.insert(host_at, part)
                out = programs[b](*(a for col in cols for a in col))
                outs.append(out)
                i += b
            return outs

        def front(bound, wait=None):
            """One pass over the front; (wall, thread, put) ms.
            ``wait``: what the thread waits for before a put, "landed":
            the put before it, "behind": the put before that one."""
            put = 0.0
            outs, at = [], 0
            flying = []
            t0 = time.perf_counter()
            for chunk in chunks_of(nbytes, bound):
                p0 = time.perf_counter()
                if wait and len(flying) > (wait == "behind"):
                    jax.block_until_ready(flying.pop(0))
                bufs = jax.device_put([tiles[i] for i in chunk],
                                      [dev] * len(chunk))
                put += time.perf_counter() - p0
                flying.append(bufs)
                outs.append(calls(bufs, at))
                at += len(chunk)
                del bufs
            t1 = time.perf_counter()
            t2 = ready(outs)
            return (t2 - t0) * 1e3, (t1 - t0) * 1e3, put * 1e3

        def put_only():
            t0 = time.perf_counter()
            bufs = jax.device_put(tiles, [dev] * n)
            t1 = time.perf_counter()
            t2 = ready(bufs)
            return (t2 - t0) * 1e3, (t1 - t0) * 1e3, bufs

        def median_of(fn):
            fn()    # every program and every size of put once, untimed
            rows = [fn() for _ in range(args.reps)]
            return [statistics.median(col) for col in zip(*rows)]

        entry = {"tiles": n, "shape": list(shape), "tile_mb": nbytes[0] / 1e6,
                 "front_gb": sum(nbytes) / 1e9, "schemes": {}}
        print(f"device {dev.device_kind}; front {name}: {n} x "
              f"{nbytes[0] / 1e6:.1f} MB", flush=True)
        for b in programs:   # build every program before any timing
            if b <= n:
                on_chip = jax.device_put(tiles[:b], [dev] * b)
                jax.block_until_ready(calls(on_chip, 0))
                del on_chip
        schemes = [("whole", None, None)] + [
            (f"chunk{b}{'_' + w if w else ''}", b * mib, w)
            for w in (None, "landed", "behind") for b in bounds]
        seen = {}
        for label, bound, wait in schemes:
            plan = chunks_of(nbytes, bound)
            key = (wait,) + tuple(len(c) for c in plan)
            if key in seen:    # the same chunks as a smaller bound's
                entry["schemes"][label] = dict(
                    entry["schemes"][seen[key]], same_as=seen[key])
                continue
            seen[key] = label
            wall, thread, put = median_of(lambda: front(bound, wait))
            entry["schemes"][label] = {
                "puts": len(plan), "tiles_a_put": len(plan[0]),
                "calls": sum(len(buckets_of(len(c))) for c in plan),
                "wall_ms": wall, "thread_ms": thread, "put_ms": put}
            print(label, json.dumps(entry["schemes"][label]), flush=True)
        wall, back = median_of(lambda: put_only()[:2])
        entry["put_only"] = {"wall_ms": wall, "return_ms": back,
                             "gb_s": sum(nbytes) / 1e6 / wall}

        def landing():
            """When each of the unthrottled puts of ``chunk<bounds[1]>``
            was issued and had landed, ms from the first: in the order
            issued, one after the other, or all near the end?"""
            t0 = time.perf_counter()
            puts, issued = [], []
            for chunk in chunks_of(nbytes, bounds[len(bounds) // 2] * mib):
                puts.append(jax.device_put([tiles[i] for i in chunk],
                                           [dev] * len(chunk)))
                issued.append((time.perf_counter() - t0) * 1e3)
            return issued, [(ready(b) - t0) * 1e3 for b in puts]

        landing()
        issued, landed = landing()
        step = max(1, len(landed) // 8)
        entry["landing_ms"] = {"issued": issued[::step],
                               "landed": landed[::step]}

        on_chip = put_only()[2]

        def calls_only():
            t0 = time.perf_counter()
            outs = calls(on_chip, 0)
            t1 = time.perf_counter()
            return (ready(outs) - t0) * 1e3, (t1 - t0) * 1e3

        def together():
            t0 = time.perf_counter()
            outs = calls(on_chip, 0)
            bufs = jax.device_put(tiles, [dev] * n)
            t1 = time.perf_counter()
            t_out = ready(outs)
            t_buf = ready(bufs)
            return ((max(t_out, t_buf) - t0) * 1e3, (t1 - t0) * 1e3,
                    (t_out - t0) * 1e3, (t_buf - t0) * 1e3)

        wall, thread = median_of(calls_only)
        entry["calls_only"] = {"wall_ms": wall, "thread_ms": thread}
        if sum(nbytes) * 3 < 12e9:   # three fronts on the chip at once
            wall, thread, outs_at, bufs_at = median_of(together)
            entry["together"] = {"wall_ms": wall, "thread_ms": thread,
                                 "calls_ready_ms": outs_at,
                                 "put_ready_ms": bufs_at}
        del on_chip
        whole = entry["schemes"]["whole"]["wall_ms"]
        entry["shorter_pct"] = {
            label: 100 * (1 - s["wall_ms"] / whole)
            for label, s in entry["schemes"].items() if label != "whole"}
        entry["hidden_pct_of_smaller_half"] = {
            label: 100 * (entry["put_only"]["wall_ms"]
                          + entry["calls_only"]["wall_ms"] - s["wall_ms"])
            / min(entry["put_only"]["wall_ms"],
                  entry["calls_only"]["wall_ms"])
            for label, s in entry["schemes"].items()}
        print(json.dumps({k: v for k, v in entry.items()
                          if k != "schemes"}), flush=True)
        report["fronts"][name] = entry
        del tiles, others, programs

    # ISSUE 44's rule
    if not args.rehearse and set(FRONTS) <= set(report["fronts"]):
        f = report["fronts"]
        passes = [label for label in f["gemm2048"]["shorter_pct"]
                  if f["gemm2048"]["shorter_pct"][label] >= 25
                  and f["update1024"]["shorter_pct"][label] >= 25
                  and f["gemm512"]["shorter_pct"][label] >= -3]
        report["rule"] = {"schemes_that_pass": passes,
                          "goes_in": bool(passes)}
        print(json.dumps(report["rule"]), flush=True)

    os.makedirs(args.out, exist_ok=True)
    out = "stage_front_probe_rehearsal.json" if args.rehearse \
        else "stage_front_probe.json"
    with open(os.path.join(args.out, out), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
