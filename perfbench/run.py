#!/usr/bin/env python3
"""One run of one cell: seconds per checked call of the entry point a
user calls, with no knob set.  In most cells the call is a
factorization of one matrix in place, and the metrics keep that name
(``factor_s`` is the seconds per timed call); what the call is otherwise
is the ``note`` of its operation file, printed with the cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process; it owns the chip(s) for its whole life.  Set-up (all of it
is ``setup_s``): imports, the native core, the input built on the host
from ``--seed``, ``parsec_tpu.init()`` with nothing set, one cold call
(compiles, or loads from the persistent cache), the calls on small grids
an operation file may ask for (``warm_up``) and one more to warm.
Then the window: a closed loop, one caller, calls back to back until
``--seconds`` have passed, each timed from the entry-point call
``entry(ctx, *operands, **args)`` to ``block_until_ready`` on every
tile's newest copy of every operand the call writes.  After the window
the warm-up result and the window's last one are held against the plain
float64 reference (``reference/``), and the last line of stdout is the
one JSON object of the contract.

It fails, printing no result, unless JAX's default backend is a TPU
whose ``device_kind`` is in ``peaks.json`` and exactly the cell's chips
are there.  ``--rehearse N,NB`` is the CPU dry run: tiny sizes, every
line marked REHEARSAL, every time and device number "not measured", and
never the contract's last line.

Which cell, configuration, traffic, operation, operand, kernel class and
metric exist is data (``spec.py``); nothing in this file names one.
"""
import sys
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import roofline, spec, xplane  # noqa: E402

DOWNGRADE_KEYS = ("batch_downgrades", "donate_retries", "mesh_downgrades")
#: calls of the window the profiler is on for in a --trace 1 run (the
#: second and third: a whole window's trace is too large to reduce), and
#: consecutive raising calls that end a window
N_TRACED = 2
MAX_RAISED_IN_A_ROW = 3
NOT_MEASURED = "not measured"


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero, print no result."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default="", metavar="N,NB",
                    help="CPU dry run at a tiny size; can never print "
                         "the contract's last line")
    return ap.parse_args(argv)


def gate_device(jax, cell, rehearse):
    """The device of the contract's ``device`` key, or Refused."""
    local = jax.local_devices()
    device = {"platform": local[0].platform, "kind": local[0].device_kind,
              "count": len(local)}
    if rehearse:
        return device, None
    if jax.default_backend() != "tpu" or device["platform"] != "tpu":
        raise Refused(f"no TPU: jax.default_backend() is "
                      f"{jax.default_backend()!r}; the benchmark never "
                      f"falls back (--rehearse N,NB is the CPU dry run)")
    peaks = spec.peaks_of(device["kind"])
    if device["count"] != cell.chips:
        raise Refused(f"cell {cell.name!r} is {cell.chips} chip(s) in the "
                      f"arrangement its configuration states; this "
                      f"process sees {device['count']}")
    return device, peaks


def accel_devices(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def snapshot(devs):
    return [dict(d.stats) for d in devs]


def deltas(devs, before):
    return [{k: v - b.get(k, 0) for k, v in d.stats.items()
             if isinstance(v, (int, float))}
            for d, b in zip(devs, before)]


def block_on_tiles(jax, written):
    """``wait()`` returns at dispatch; the work is done when every
    tile's newest copy is ready, of every collection the call wrote."""
    jax.block_until_ready([A.data_of(*c).newest_copy().payload
                           for A in written for c in A.tiles()])


def seeded_input(ref, cell, seed):
    """The reference's input from the seed in the configuration's
    storage type: one array, or a dict of arrays by operand name."""
    import numpy as np
    dtype = np.dtype(cell.config["storage_dtype"])
    M = ref.make_input(cell.sizes["N"], seed)
    if isinstance(M, dict):
        return {k: v.astype(dtype, copy=False) for k, v in M.items()}
    return M.astype(dtype, copy=False)


def memory_peak(jax):
    """peak_bytes_in_use on the fullest chip."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices()))


def memory_in_use(jax):
    """bytes_in_use now, summed over the chips."""
    return int(sum((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


class HostClocks:
    """Seconds the interpreter's garbage collector ran, and seconds and
    counts of programs built or loaded (jax.monitoring backend-compile
    events; how many the persistent cache answered), since the process
    started.  The collector holds the interpreter lock, so its seconds
    stall every thread of the runtime."""

    def __init__(self, jax):
        self.gc_s = self.load_s = 0.0
        self.loads = self.cache_hits = 0
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _on_secs(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.loads += 1
            self.load_s += duration

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def read(self):
        return {"gc_s": self.gc_s, "load_s": self.load_s,
                "loads": self.loads, "cache_hits": self.cache_hits}


class Factorizer:
    """The timed path: one object, built in set-up and handed to the
    window."""

    def __init__(self, jax, ctx, cell, M, clocks):
        self.jax, self.ctx, self.M, self.clocks = jax, ctx, M, clocks
        self.entry = cell.entry()
        self.collection = cell.collection()
        self.names = [name for name, _ in cell.operands]
        self.written = [name for name, mode in cell.operands
                        if mode == "inout"]
        self.args = cell.args
        self.nb = cell.sizes["NB"]
        self.devs = accel_devices(ctx)
        self.want_tasks = cell.n_tasks()
        self.refill = cell.traffic["matrix"] == "refilled"
        self.tiled = None

    def tile(self):
        """The input tiled for the next call (host work, outside the
        timer), one square collection per operand in the entry point's
        order: by the traffic file either the caller's tiled matrices
        filled again from the input, every one of them, or new
        collections each time."""
        M = self.M if isinstance(self.M, dict) else {self.names[0]: self.M}
        if self.tiled is None or not self.refill:
            self.tiled = {}
            for name in self.names:
                n = M[name].shape[0]
                self.tiled[name] = self.collection(n, n, self.nb, self.nb,
                                                   dtype=M[name].dtype)
        return {name: A.from_numpy(M[name])
                for name, A in self.tiled.items()}

    def pull(self, operands):
        """What the call wrote, on the host, as the reference's
        ``residual`` takes it: the one ``inout`` operand's array, or a
        dict by name where there are several."""
        out = {name: operands[name].to_numpy() for name in self.written}
        return out if len(out) > 1 else out[self.written[0]]

    def factor(self, operands):
        """One timed call.  (wall seconds, per-device counter deltas,
        why it failed or None)."""
        before = snapshot(self.devs)
        host0 = self.clocks.read()
        written = [operands[name] for name in self.written]
        t0 = time.perf_counter()
        self.entry(self.ctx, *operands.values(), **self.args)
        block_on_tiles(self.jax, written)
        wall = time.perf_counter() - t0
        d = deltas(self.devs, before)
        # the host's own clocks ride with the first device's counters
        d[0].update({k: v - host0[k]
                     for k, v in self.clocks.read().items()})
        ran = sum(x.get("tasks", 0) for x in d)
        why = None
        if ran != self.want_tasks:
            why = (f"{ran} tasks ran on accelerator devices, the DAG has "
                   f"{self.want_tasks}")
        for key in DOWNGRADE_KEYS:
            moved = sum(x.get(key, 0) for x in d)
            if moved:
                why = f"{key} moved by {moved}: a fast rung gave way"
        return wall, d, why


def warm_smaller(fz, cell):
    """The operation file's ``warm_up``: calls of the entry point on
    small grids of the cell's tile, zeros in them, between the cold call
    and the warm one.  A k-level of 2 to 16 ready tasks reaches the
    manager whole, or a task or two ahead of the rest, and is dispatched
    as stacked calls of 2, 4 and 8, so these calls build here the small
    buckets that a call at the cell's size meets once in a dozen calls,
    which would else be built in the window.  Nothing of
    them is checked but that no fast rung gave way.  Returns (programs
    built or loaded, of them from the persistent cache, what failed)."""
    import numpy as np
    dtype, nb = np.dtype(cell.config["storage_dtype"]), fz.nb
    tiled = [{name: (fz.collection(r * nb, c * nb, nb, nb, dtype=dtype),
                     np.zeros((r * nb, c * nb), dtype))
              for name, (r, c) in grid.items()}
             for grid in cell.warm_up_grids()]
    before, host0 = snapshot(fz.devs), fz.clocks.read()
    for _ in range(cell.warm_up["rounds"]):
        for grid in tiled:
            operands = {name: A.from_numpy(Z) for name, (A, Z) in grid.items()}
            fz.entry(fz.ctx, *operands.values(), **fz.args)
            block_on_tiles(fz.jax, [operands[name] for name in fz.written])
    moved = {key: sum(x.get(key, 0) for x in deltas(fz.devs, before))
             for key in DOWNGRADE_KEYS}
    host = fz.clocks.read()
    return (host["loads"] - host0["loads"],
            host["cache_hits"] - host0["cache_hits"],
            [f"small set-up calls: {key} moved by {n}: a fast rung gave way"
             for key, n in moved.items() if n])


def run_window(fz, seconds, trace_dir):
    """The closed loop; with a ``trace_dir`` the profiler is on for the
    window's second to (1 + N_TRACED)th call.  Returns what the window
    saw: walls, per-call counter deltas, bytes in use after each,
    failure reasons, the count started, the tiled operands of the last
    call that finished, the count traced."""
    jax = fz.jax
    w = types.SimpleNamespace(walls=[], per_factor=[], in_use=[],
                              reasons=[], attempted=0, last=None,
                              n_traced=0)
    raised_in_a_row = 0
    profiling = False
    w0 = time.perf_counter()
    with contextlib.ExitStack() as tracing:     # stops the profiler
        while time.perf_counter() - w0 < seconds:
            if trace_dir and w.attempted == 1:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing.callback(jax.profiler.stop_trace)
                tracing.enter_context(
                    jax.profiler.TraceAnnotation("perfbench:traced"))
                profiling = True
            w.attempted += 1
            try:
                with jax.profiler.TraceAnnotation("perfbench:tile_input"):
                    operands = fz.tile()
                with jax.profiler.TraceAnnotation("perfbench:entry_call"):
                    wall, d, why = fz.factor(operands)
            except Exception:   # the boundary: count it, go on
                w.reasons.append(f"call {w.attempted} raised:\n"
                                 f"{traceback.format_exc()}")
                raised_in_a_row += 1
                if raised_in_a_row >= MAX_RAISED_IN_A_ROW:
                    break
                continue
            raised_in_a_row = 0
            w.last = operands
            w.walls.append(wall)
            w.per_factor.append(d)
            w.in_use.append(memory_in_use(jax))
            if why:
                w.reasons.append(f"call {w.attempted}: {why}")
            if profiling:
                w.n_traced += 1
                if w.n_traced == N_TRACED:
                    tracing.close()
                    profiling = False
    return w


def sum_counters(per_factor):
    """(summed over calls and devices, summed per device)."""
    if not per_factor:
        return {}, []
    by_dev = []
    for i in range(len(per_factor[0])):
        acc = {}
        for d in per_factor:
            for k, v in d[i].items():
                acc[k] = acc.get(k, 0) + v
        by_dev.append(acc)
    total = {}
    for acc in by_dev:
        for k, v in acc.items():
            total[k] = total.get(k, 0) + v
    return total, by_dev


def check_factors(ref, cell, M, seed, say, factors):
    """The comparison that decides ``correct``: after the window,
    outside set-up, each result pulled from the chip against the plain
    reference, the number printed beside its limit.  Returns what
    missed, and every number compared with its limit by a short name."""
    limit = float(cell.config["check"]["limit"])
    exp = ref.expected(M, seed)
    misses, compared = [], {}
    for label, factor in factors.items():
        if factor is None:
            misses.append(f"{label}: no result to check")
            continue
        res = ref.residual(factor, exp)
        ok = bool(res <= limit)     # a NaN residual is not correct
        # the last line stays JSON whatever the number: inf and nan go
        # as their names
        compared[label.replace(" ", "_").replace("-", "_") + "_residual"] \
            = {"value": res if math.isfinite(res) else repr(res),
               "limit": limit}
        say(f"check {label}: residual {res:.6e} against limit {limit:g}: "
            f"{'ok' if ok else 'MISSED'}")
        if not ok:
            misses.append(f"{label} call missed its tolerance")
    return misses, compared


def run_cell(args, say):
    """Everything but argument parsing and the exit code.  Returns the
    result object of the contract's last line."""
    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload)
    rehearse = bool(args.rehearse)
    if rehearse:
        n, nb = (int(x) for x in args.rehearse.split(","))
        cell.resize(N=n, NB=nb)
    mca_env = sorted(k for k in os.environ if k.startswith("PARSEC_MCA_"))
    if mca_env:
        raise Refused(f"{mca_env} set in the environment: the cells run "
                      f"with no knob set")
    if args.seed < 0:
        raise Refused("--seed is a whole number from 0")

    import jax
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    device, peaks = gate_device(jax, cell, rehearse)
    try:
        import parsec_tpu
        from parsec_tpu import native
        from parsec_tpu.utils.params import params
    except ImportError as exc:
        raise Refused(f"the program is not in this checkout: {exc}") from exc
    if not native.available:
        raise Refused("parsec_tpu.native did not build (g++ error above)")
    for k, v in cell.config.get("mca", {}).items():
        params.set_cmdline(k, str(v))
    say(f"cell {cell.name}: {cell.op_name} {cell.sizes}, "
        f"{cell.n_tasks()} tasks {cell.kernel_counts()}, "
        f"{cell.flops() / 1e12:.3f} TFLOP, matmul precision "
        f"{cell.config['matmul_precision']}, mca {cell.config.get('mca', {})}")
    say(f"one call is {cell.op['entry']}(ctx, "
        f"{', '.join(f'{n} [{m}]' for n, m in cell.operands)}"
        f"{''.join(f', {k}={v!r}' for k, v in cell.args.items())}): "
        f"{cell.op.get('note', '')}")
    say(f"device {device}; jax {jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")

    clocks = HostClocks(jax)

    ref = cell.reference()
    t = time.perf_counter()
    M = seeded_input(ref, cell, args.seed)
    # a time taken on the CPU is never printed: a rehearsal shows counts
    sec = (lambda x: NOT_MEASURED) if rehearse else (lambda x: f"{x:.4f}")
    shapes = {k: (v.shape, str(v.dtype)) for k, v in M.items()} \
        if isinstance(M, dict) else f"{M.shape} {M.dtype}"
    say(f"input {shapes} from seed {args.seed} in "
        f"{sec(time.perf_counter() - t)} s")
    ctx = parsec_tpu.init()
    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_") if args.trace \
        else None
    try:
        fz = Factorizer(jax, ctx, cell, M, clocks)
        if not fz.devs:
            raise Refused("init() attached no accelerator device")
        say(f"runtime devices {[d.name for d in ctx.devices]}, "
            f"{ctx.nb_cores} worker threads")
        setup_fail = []
        first_factor_s, _, why = fz.factor(fz.tile())
        peak_first = memory_peak(jax)
        if why:
            setup_fail.append(f"cold call: {why}")
        if cell.warm_up:
            t = time.perf_counter()
            loads, hits, fails = warm_smaller(fz, cell)
            setup_fail += fails
            say(f"{cell.warm_up['rounds']} rounds of "
                f"{len(cell.warm_up['grids'])} calls on small grids in "
                f"{sec(time.perf_counter() - t)} s: {loads} programs built "
                f"or loaded, {hits} of them from the persistent cache; "
                f"peak_bytes_in_use {memory_peak(jax)}")
        operands = fz.tile()
        warm_s, _, why = fz.factor(operands)
        if why:
            setup_fail.append(f"warm-up call: {why}")
        warm_factor = fz.pull(operands)
        del operands
        setup_s = time.perf_counter() - T_START
        say(f"set-up {sec(setup_s)} s: first call "
            f"{sec(first_factor_s)} s, second {sec(warm_s)} s; "
            f"peak_bytes_in_use {peak_first} after the first, "
            f"{memory_peak(jax)} after the second")

        w = run_window(fz, args.seconds, trace_dir)
        peak = memory_peak(jax)
        last_factor = fz.pull(w.last) if w.last is not None else None
    finally:
        ctx.fini()

    misses, compared = check_factors(ref, cell, M, args.seed, say, {
        "warm-up": warm_factor, "last of the window": last_factor})
    reasons = setup_fail + w.reasons + misses
    for r in reasons:
        say(f"FAILED: {r}")

    counters, by_dev = sum_counters(w.per_factor)
    least, rows = roofline.least_time(
        cell.kernels, cell.sizes,
        peaks or {"flops_per_s": float("inf"),
                  "hbm_bytes_per_s": float("inf")})
    obs = {
        "chips": cell.chips, "n_tasks": cell.n_tasks(), "walls": w.walls,
        "mean_wall_s": statistics.fmean(w.walls) if w.walls else None,
        "n_counted": len(w.per_factor), "counters": counters,
        "counters_by_device": by_dev, "first_factor_s": first_factor_s,
        "setup_s": setup_s,
        "memory_peak_bytes": peak, "memory_peak_first_bytes": peak_first,
        "memory_in_use_bytes": w.in_use, "least_time_s": least,
        "n_traced": w.n_traced, "trace": None,
    }
    if w.walls:
        say(f"window: {w.attempted} started, {len(w.walls)} finished; wall "
            f"mean {sec(obs['mean_wall_s'])} s, median "
            f"{sec(statistics.median(w.walls))}, min {sec(min(w.walls))}, "
            f"slowest {sec(max(w.walls))}; "
            f"{sec(cell.flops() / obs['mean_wall_s'] / 1e9)} GFLOP/s at "
            f"the mean; counters {counters}")
        say(f"walls: {[sec(x) for x in w.walls]}")
        say(f"inside the timed calls: {counters['loads']} programs built "
            f"or loaded ({counters['cache_hits']} from the persistent "
            f"cache), {sec(counters['load_s'])} s in them; "
            f"{sec(counters['gc_s'])} s of garbage collection")
        for key in ("load_s", "gc_s"):
            say(f"per call, {key}: "
                f"{[sec(d[0][key]) for d in w.per_factor]}")
    breakdown = None
    if args.trace:
        try:
            tr = xplane.reduce(xplane.read(xplane.load(
                xplane.find_xplane(trace_dir))))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if tr is None and not rehearse:
            raise Refused("the trace holds no device operation")
        obs["trace"] = tr
        if tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {
                "device_ops": xplane.top(tr["ops_s"] or tr["modules_s"]),
                "idle_gaps": xplane.top(tr["idle_by_label_s"], 4)
                + [[f"longest:{n}", s] for n, s in tr["longest_gaps"][:6]]}
            say(f"trace of {w.n_traced} call(s): window "
                f"{tr['window_s']:.4f} s, busy {tr['busy_s']:.4f} s "
                f"(by chip {tr['busy_by_chip_s']}); modules "
                f"{xplane.top(tr['modules_s'], 5)}")
            say("least time per class (count, seconds of one task, "
                f"bound): {rows}; DAG total {least:.6f} s on one chip")

    metrics = {}
    for m in cell.metrics["per_layer" if args.trace else "end_to_end"]:
        reader = spec.metric_reader(m["name"])
        value = reader.read(obs)
        if value is None:
            continue
        if rehearse and not getattr(reader, "COUNT", False):
            value = NOT_MEASURED
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = NOT_MEASURED if rehearse else peak
    result = {"correct": not setup_fail and not misses,
              "attempted": w.attempted,
              "failed": min(w.attempted, len(w.reasons) + len(misses)),
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    # each number compared beside its limit: the result's last key, and
    # the run's last lines on standard error
    result["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} limit {c['limit']:g}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None):
    args = parse_args(argv)
    prefix = "REHEARSAL " if args.rehearse else ""

    def say(msg):
        print(f"{prefix}{msg}", flush=True)

    try:
        result = run_cell(args, say)
    except (Refused, spec.SpecError) as exc:
        print(f"perfbench: REFUSED: {exc}", file=sys.stderr, flush=True)
        return 2
    if args.rehearse:
        say("result (a rehearsal is never a result): "
            + json.dumps(result))
        say("no result line: this was a CPU dry run")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
