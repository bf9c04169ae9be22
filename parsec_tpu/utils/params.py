"""MCA-style layered configuration parameters.

Reference behavior reproduced: PaRSEC registers typed, named parameters per
subsystem and resolves them from (in priority order) command line
``--mca name value``, environment ``PARSEC_MCA_<name>``, per-user/system config
files, and compiled defaults (ref: parsec/utils/mca_param.c, SURVEY.md §5.6).

This is the TPU-native re-design: a small registry with the same resolution
order; no libc, the config file format is ``name = value`` lines.
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

_ENV_PREFIX = "PARSEC_MCA_"
_lock = threading.RLock()


@dataclass
class Param:
    name: str
    type: str  # "int" | "string" | "sizet" | "bool"
    default: Any
    help: str = ""
    # resolution cache
    _value: Any = None
    _source: str = "default"
    _resolved: bool = False

    def _coerce(self, raw: Any) -> Any:
        if self.type == "int":
            return int(raw)
        if self.type == "sizet":
            v = int(str(raw), 0)
            if v < 0:
                raise ValueError(f"sizet param {self.name} must be >= 0, got {v}")
            return v
        if self.type == "bool":
            if isinstance(raw, bool):
                return raw
            return str(raw).strip().lower() in ("1", "true", "yes", "on")
        return str(raw)


class ParamRegistry:
    """Registry of MCA parameters with layered resolution."""

    def __init__(self) -> None:
        self._params: Dict[str, Param] = {}
        self._cmdline: Dict[str, str] = {}
        self._file_values: Dict[str, str] = {}
        self._files_loaded = False
        # scoped-override bookkeeping (cmdline_override): per name, the
        # pre-override state plus a stack of live override tokens, so
        # CONCURRENT overrides of one name from several threads (spmd
        # rank threads all entering the same context manager) unwind to
        # the true original instead of each other's values
        self._overrides: Dict[str, Dict[str, Any]] = {}

    # -- registration ------------------------------------------------------
    def register(self, name: str, type: str, default: Any, help: str = "") -> Param:
        with _lock:
            p = self._params.get(name)
            if p is None:
                p = Param(name=name, type=type, default=default, help=help)
                self._params[name] = p
            return p

    def reg_int(self, name: str, default: int, help: str = "") -> Param:
        return self.register(name, "int", default, help)

    def reg_sizet(self, name: str, default: int, help: str = "") -> Param:
        return self.register(name, "sizet", default, help)

    def reg_string(self, name: str, default: Optional[str], help: str = "") -> Param:
        return self.register(name, "string", default, help)

    def reg_bool(self, name: str, default: bool, help: str = "") -> Param:
        return self.register(name, "bool", default, help)

    # -- external value sources -------------------------------------------
    def set_cmdline(self, name: str, value: str) -> None:
        with _lock:
            self._cmdline[name] = value
            p = self._params.get(name)
            if p is not None:
                p._resolved = False

    def unset_cmdline(self, name: str) -> None:
        """Remove a cmdline-layer override (lower layers shine through
        again); no-op when none is set."""
        with _lock:
            self._cmdline.pop(name, None)
            p = self._params.get(name)
            if p is not None:
                p._resolved = False

    def get_cmdline(self, name: str) -> Optional[str]:
        """Raw cmdline-layer override for ``name`` (None when unset).
        The public accessor for embedders that save/restore overrides —
        the supported alternative to reaching into the private dict."""
        with _lock:
            return self._cmdline.get(name)

    @contextmanager
    def cmdline_override(self, name: str, value: str):
        """Scoped cmdline-layer override: sets ``name`` for the body,
        then restores whatever cmdline value (or absence) was there
        before — safe to nest, exception-safe, and safe under
        CONCURRENT same-name overrides from several threads.

        The naive save/restore (capture ``get_cmdline`` on enter, put
        it back on exit) leaks under concurrency: thread B entering
        while thread A's override is live captures *A's value* as its
        "previous" state and restores it at exit — permanently, once A
        has also exited (the test_stagec-before-test_overlap_pipeline
        ordering flake: spmd rank threads overriding ``stage_compile``
        concurrently left it set for every later test).  Instead each
        enter pushes a token onto a per-name stack that remembers the
        TRUE pre-override state from the first push; each exit removes
        its own token and re-resolves to the top remaining override or
        the original, whichever the stack says."""
        tok = object()
        with _lock:
            ent = self._overrides.get(name)
            if ent is None:
                ent = {"had": name in self._cmdline,
                       "orig": self._cmdline.get(name),
                       "stack": []}
                self._overrides[name] = ent
            ent["stack"].append((tok, value))
            self._cmdline[name] = value
            p = self._params.get(name)
            if p is not None:
                p._resolved = False
        try:
            yield self
        finally:
            with _lock:
                ent = self._overrides.get(name)
                if ent is not None:
                    ent["stack"] = [tv for tv in ent["stack"]
                                    if tv[0] is not tok]
                    if ent["stack"]:
                        # LIFO by surviving pushes: the most recent
                        # still-live override wins (nesting semantics)
                        self._cmdline[name] = ent["stack"][-1][1]
                    else:
                        del self._overrides[name]
                        if ent["had"]:
                            self._cmdline[name] = ent["orig"]
                        else:
                            self._cmdline.pop(name, None)
                p = self._params.get(name)
                if p is not None:
                    p._resolved = False

    def parse_argv(self, argv: List[str]) -> List[str]:
        """Consume ``--mca name value`` / ``--parsec name=value`` pairs.

        Returns argv with consumed options removed (ref: parsec/parsec.c:418-454).
        """
        out: List[str] = []
        i = 0
        while i < len(argv):
            a = argv[i]
            if a == "--mca":
                if i + 2 > len(argv) - 1:
                    raise ValueError("--mca requires <name> <value>")
                self.set_cmdline(argv[i + 1], argv[i + 2])
                i += 3
                continue
            if a.startswith("--mca="):
                body = a[len("--mca="):]
                if "=" not in body:
                    raise ValueError("--mca=<name>=<value> expected")
                k, v = body.split("=", 1)
                self.set_cmdline(k, v)
                i += 1
                continue
            if a == "--parsec" and i + 1 < len(argv):
                body = argv[i + 1]
                if "=" in body:
                    k, v = body.split("=", 1)
                    self.set_cmdline(k, v)
                i += 2
                continue
            out.append(a)
            i += 1
        return out

    def _load_files(self) -> None:
        if self._files_loaded:
            return
        self._files_loaded = True
        paths = []
        sysconf = os.environ.get("PARSEC_SYSCONF_PARAMS")
        if sysconf:
            paths.append(sysconf)
        home = os.path.expanduser("~/.parsec/mca-params.conf")
        paths.append(home)
        for path in paths:
            try:
                with open(path) as fh:
                    for line in fh:
                        line = line.strip()
                        if not line or line.startswith("#"):
                            continue
                        if "=" in line:
                            k, v = line.split("=", 1)
                            self._file_values[k.strip()] = v.strip()
            except OSError:
                continue

    # -- resolution --------------------------------------------------------
    def get(self, name: str) -> Any:
        with _lock:
            p = self._params.get(name)
            if p is None:
                raise KeyError(f"unknown MCA parameter: {name}")
            if p._resolved:
                return p._value
            self._load_files()
            if name in self._cmdline:
                p._value, p._source = p._coerce(self._cmdline[name]), "cmdline"
            elif _ENV_PREFIX + name in os.environ:
                p._value, p._source = p._coerce(os.environ[_ENV_PREFIX + name]), "env"
            elif name in self._file_values:
                p._value, p._source = p._coerce(self._file_values[name]), "file"
            else:
                p._value, p._source = p.default, "default"
            p._resolved = True
            return p._value

    def source(self, name: str) -> str:
        self.get(name)
        return self._params[name]._source

    def get_or(self, name: str, type: str, default: Any) -> Any:
        with _lock:
            if name not in self._params:
                self.register(name, type, default)
            return self.get(name)

    def dump(self) -> Dict[str, Any]:
        return {n: self.get(n) for n in sorted(self._params)}

    def reset(self) -> None:
        """Test helper: clear caches so env changes are re-read."""
        with _lock:
            self._cmdline.clear()
            self._file_values.clear()
            self._files_loaded = False
            for p in self._params.values():
                p._resolved = False


#: process-wide registry (mirrors the global MCA repository)
params = ParamRegistry()


def register_core_params() -> None:
    """Default knobs carried over from the reference (SURVEY.md §5.6)."""
    params.reg_string("sched", "lfq", "scheduler module to use")
    params.reg_string("bind_threads", "",
                      "worker core binding: \"rr\" or a core list \"0,2,4\" (ref --parsec_bind)")
    params.reg_bool("ptg_codegen", True,
                    "generate per-task-class successor/goal code (jdf2c analog)")
    params.reg_string("ptg_dep_management", "hash",
                      "PTG dependency tracking: hash (dynamic table) | "
                      "static (lowered dense counters + native engine; "
                      "single-rank, ref --dep-management=index-array)")
    params.reg_sizet("debug_history_size", 0,
                     "debug history ring entries (0=off, ref PARSEC_DEBUG_HISTORY)")
    params.reg_int("dtd_window_size", 8000, "DTD sliding window size")
    params.reg_int("dtd_threshold_size", 4000, "DTD backpressure resume threshold")
    params.reg_string("runtime_comm_coll_bcast", "binomial",
                      "broadcast topology: star|chain|binomial")
    params.reg_sizet("runtime_comm_short_limit", 4096,
                     "max payload inlined in an activate message")
    params.reg_bool("comm_adaptive_short_limit", False,
                    "tune the eager/rendezvous cutoff per peer from the "
                    "measured GET round-trip and link bandwidth (the "
                    "static runtime_comm_short_limit is the floor, "
                    "comm_short_limit_max the ceiling)")
    params.reg_sizet("comm_short_limit_max", 1 << 20,
                     "ceiling for the adaptive eager/rendezvous cutoff")
    params.reg_sizet("comm_coalesce_max_bytes", 1 << 16,
                     "max bytes of queued small AMs coalesced into one "
                     "wire frame/syscall on the TCP transport (0 = one "
                     "frame per message)")
    params.reg_sizet("comm_chunk_bytes", 1 << 17,
                     "buffers at least this large stream as bounded "
                     "chunk frames so control messages interleave with "
                     "bulk data (TCP transport)")
    params.reg_int("comm_compress_threshold_mbps", 0,
                   "engage negotiated per-link compression when the "
                   "measured send bandwidth EWMA drops below this many "
                   "MB/s and a sample probe shows the traffic "
                   "compresses (0 = never)")
    params.reg_string("comm_quantize", "",
                      "lossy quantized wire codec for bulk float tile "
                      "payloads (bf16 | int8): engaged per link toward "
                      "peers that advertised it at the HELLO (both ends "
                      "must set the knob); control AMs, checkpoint "
                      "shards and non-float buffers always stay "
                      "lossless. Empty = off, bit-for-bit unchanged "
                      "wire")
    params.reg_int("comm_quantize_threshold_mbps", 0,
                   "engage the quantized codec only when the send-"
                   "bandwidth EWMA toward the peer is below this many "
                   "MB/s (0 = whenever comm_quantize is set — the "
                   "knob itself is the lossy opt-in)")
    params.reg_sizet("comm_send_buffer_bytes", 1 << 26,
                     "per-peer bounded send buffer: send_am blocks "
                     "while this many bytes are queued ahead of it "
                     "(backpressure toward slow links)")
    params.reg_string("comm_reconnect_timeout", "",
                      "reliable TCP sessions: keep a torn peer link in "
                      "SUSPECT and retry reconnecting (with seq-"
                      "numbered frame replay) for up to this many "
                      "seconds before escalating to rank failure; "
                      "empty/0 = off (every socket error is fail-fast, "
                      "the pre-session behavior)")
    params.reg_string("comm_reconnect_backoff", "",
                      "initial reconnect backoff in seconds (default "
                      "0.05), doubling with jitter up to a 2 s ceiling "
                      "while the reconnect budget lasts")
    params.reg_sizet("comm_replay_window_bytes", 1 << 24,
                     "per-peer replay window: sent-but-unacked session "
                     "frames retained for replay after a reconnect; at "
                     "the cap the writer pauses data frames until the "
                     "peer's cumulative acks drain it (retained bytes "
                     "also count against comm_send_buffer_bytes)")
    params.reg_int("arena_max_used", -1, "cap on arena allocated buffers (-1 off)")
    params.reg_int("arena_max_cached", -1, "cap on arena cached buffers (-1 off)")
    params.reg_int("task_startup_chunk", 256, "startup enumerator chunk size")
    params.reg_bool("runtime_keep_highest_priority_task", True,
                    "keep best ready task on releasing thread, bypass scheduler")
    params.reg_string("profile", "", "enable profiling; path prefix for traces")
    params.reg_bool("metrics", False,
                    "collect runtime metrics (latency histograms + comm/"
                    "device counters) without full trace capture; "
                    "exposition via obs.prometheus / the aggregator")
    params.reg_bool("obs_flow", False,
                    "cross-rank flow tracing (ISSUE 15): stamp data-"
                    "plane messages with a (origin, span) trace "
                    "context negotiated via the HELLO \"tr\" "
                    "capability, estimate per-peer clock offsets from "
                    "extended ping/pong exchanges, and emit Chrome-"
                    "trace flow events so tools/obs_trace_merge.py "
                    "can fuse rank timelines; off (default) keeps "
                    "every wire byte bit-for-bit unchanged")
    params.reg_bool("obs_live", False,
                    "in-runtime streaming health monitor (ISSUE 16): "
                    "fold closing comm/device/exec spans and stitched "
                    "flow pairs into rolling-window per-link exposed-"
                    "wait, per-rank overlap, per-link flow lag, and "
                    "per-taskpool attribution (the flow context grows "
                    "a taskpool wire id + send timestamp toward peers "
                    "that negotiated the HELLO \"lv\" capability); an "
                    "anomaly layer fires straggler / degraded-link / "
                    "stuck-progress detectors against self-calibrated "
                    "baselines, each firing a trace annotation plus "
                    "PARSEC::OBS::HEALTH::* gauges; snapshots ride "
                    "sde_push so the aggregator serves GET /health.  "
                    "Implies the obs_flow machinery; off (default) is "
                    "bit-for-bit inert: no threads, no gauges, no "
                    "wire change")
    params.reg_int("obs_live_window_ms", 250,
                   "rolling-window tick of the obs_live monitor: "
                   "detector baselines fold one sample per window "
                   "(smaller = faster detection, noisier baselines)")
    params.reg_bool("tune_auto", False,
                    "closed-loop self-tuning (ISSUE 17): a controller "
                    "rides the obs_live window tick and adapts per-link "
                    "quantized codec choice (runtime K_TUNE "
                    "renegotiation toward peers that advertised the "
                    "HELLO \"tn\" capability), the device pipeline "
                    "shape (device_batch_max / device_prefetch_depth, "
                    "hill-climbed with revert-on-regress), and stagec "
                    "exclude decisions (stage_compile_exclude fed from "
                    "repeat straggler firings). Every move emits a "
                    "tune:* annotation on "
                    "the health stream plus PARSEC::TUNE::* gauges. "
                    "Implies obs_live; off (default) constructs "
                    "nothing and is bit-for-bit inert on the wire")
    params.reg_string("tune_residual_budget", "1e-2",
                      "max relative residual the codec ladder may "
                      "spend: qbf16 (~1e-2) needs budget >= 1e-2, "
                      "qint8 (~1e-1) needs budget >= 1e-1; 0 pins "
                      "every link lossless (the controller still "
                      "tunes the device pipeline)")
    params.reg_int("tune_hysteresis_windows", 2,
                   "consecutive agreeing health windows required "
                   "before the controller moves a knob (and the "
                   "cool-down after any move/revert) — larger = "
                   "steadier under oscillating signal, slower to "
                   "react")
    params.reg_string("profiling_dot", "",
                      "capture the executed DAG; path prefix for DOT files "
                      "(ref: --parsec_dot)")
    params.reg_string("termdet", "local", "termination detection module")
    params.reg_sizet("tpu_memory_fraction_pct", 85,
                     "percent of HBM managed by the arena")
    params.reg_int("device_batch_max", 16,
                   "max same-class ready tasks stacked into one jitted "
                   "device dispatch (<=1 disables batching: every task "
                   "is its own XLA submission, the pre-batching "
                   "behavior)")
    params.reg_string("device_mesh_shape", "",
                      "attach this rank's XLA chips as ONE mesh device "
                      "(\"PxQ\" grid or a chip count, e.g. \"2x2\" or "
                      "\"4\"): tiles are placed block-cyclically across "
                      "the chips and batched dispatch compiles through "
                      "shard_map so one jitted call executes a batch "
                      "spread over the mesh; intra-mesh dependencies "
                      "ride XLA transfers/collectives instead of the "
                      "wire. Empty = one device per chip (the "
                      "pre-mesh behavior); falls back per-chip when "
                      "the jax build lacks shard_map or too few chips "
                      "exist")
    params.reg_bool("comm_mesh_local", True,
                    "ship device-array payloads by reference (no "
                    "serialize/deserialize) to peers that share this "
                    "process's XLA client — the mesh-local fast path; "
                    "off forces every payload through host bytes")
    params.reg_int("device_prefetch_depth", 4,
                   "the stage compiler's prestager: at most this many "
                   "pending stages hold outstanding early stage-ins "
                   "(0 = none).  The classic path reads it no more: "
                   "its manager stages a drained ready set whole")
    params.reg_bool("stage_compile", False,
                    "whole-stage DAG->XLA compilation (stagec/, ISSUE "
                    "12): lower verified PTG stages into fused jitted "
                    "programs executed as single chores, with the "
                    "interpreted batched dispatch as the residue/"
                    "fallback path; off (default) keeps the per-task "
                    "runtime bit-for-bit")
    params.reg_int("stage_compile_max_tasks", 1024,
                   "max task instances fused into one compiled stage "
                   "(bounds trace size / compile time; larger stages "
                   "amortize dispatch further — cross-stage boundaries "
                   "pay an interpreted release walk per boundary task)")
    params.reg_bool("stage_compile_shard", True,
                    "compile eligible wave-front stages through "
                    "shard_map over the rank's chip mesh "
                    "(device_mesh_shape) so one compiled stage spans "
                    "chips; off forces the fused single-chip callable")
    params.reg_bool("stage_compile_chain", True,
                    "cross-pool stage chaining (stagec/chain.py, ISSUE "
                    "13): when a taskpool sequence is declared "
                    "(stagec.chain.declare_chain / ops.dposv), fuse the "
                    "final stage of pool K with the first stage of pool "
                    "K+1 into one chained program when the inter-pool "
                    "dataflow is provable; off runs each pool's stages "
                    "separately (the PR 12 per-pool behavior)")
    params.reg_bool("stage_residue_batch", True,
                    "compiled residue schedule (ISSUE 13): dispatch "
                    "per-(level, class) residue groups pre-planned at "
                    "stage-plan time straight onto the device batching "
                    "pipeline, skipping the per-task scheduler "
                    "round-trip; off keeps the PR 12 per-task residue "
                    "dispatch")
    params.reg_string("stage_compile_exclude", "",
                      "comma-separated task-class names excluded from "
                      "stage lowering (verdict STG306): their instances "
                      "run as interpreted residue — a debugging / "
                      "measurement knob (the residue-heavy bench leg "
                      "rides it)")
    params.reg_bool("stage_compile_xrank", False,
                    "cross-rank SPMD stages (stagec/xrank.py, ISSUE 20): "
                    "lower a wave-front stage that spans ranks into ONE "
                    "shard_map program over a global mesh of the "
                    "participating ranks' lane devices, turning inter-"
                    "rank dependency edges into in-program collectives "
                    "(all-gather of the boundary tiles) with control-"
                    "only activations on the wire; negotiated per peer "
                    "via the HELLO \"xs\" capability — mixed-version or "
                    "knob-unset peers keep the activation path bit-for-"
                    "bit; off (default) keeps every stage rank-local")
    params.reg_string("stage_xrank_timeout", "60",
                      "seconds a rank waits at a cross-rank stage "
                      "rendezvous before downgrading that stage to its "
                      "rank-local fallback (the peers decline and fall "
                      "back too — the ladder never hangs termdet)")
    params.reg_bool("stage_compile_donate", True,
                    "donate-by-default inside compiled stages (ISSUE "
                    "20c): donate stale device buffers of WRITE slots "
                    "whose member classes the BDY204 analysis proves "
                    "free of intra-stage tile aliasing — no "
                    "device_donate opt-in needed; by-reference payload "
                    "shipping (mesh-local / cross-rank parks) switches "
                    "to defensive device copies while stage donation "
                    "is live so no shipped buffer is invalidated under "
                    "a consumer; off restores the PR 12 opt-in-only "
                    "donation")
    params.reg_int("comm_prefetch_inflight", 8,
                   "max rendezvous GETs prefetched for activations that "
                   "arrived ahead of their taskpool's registration/"
                   "startup counts: the payload fetch overlaps the tail "
                   "of the previous pool instead of serializing behind "
                   "counts_ready (0 = no GET prefetch)")
    params.reg_bool("sched_dynamic_priority", True,
                    "critical-path-driven scheduling: an online per-"
                    "class profile (duration-weighted EWMA fed from "
                    "device dispatch + CPU exec timings) computes an "
                    "upward-rank boost per task class; priority "
                    "schedulers pop critical-path classes first, with "
                    "the PTG spec's static priority as the tiebreak")
    params.reg_bool("device_donate", False,
                    "donate stale device input buffers of WRITE flows "
                    "to the batched call (jax donate_argnums) to cut "
                    "HBM churn; see the guide's donation caveats")
    params.reg_string("sde_push", "",
                      "host:port of a live counter aggregator to push SDE "
                      "snapshots to (ref: tools/aggregator_visu)")
    params.reg_int("sde_push_interval_ms", 1000,
                   "milliseconds between SDE pushes")
    params.reg_bool("comm_thread", False,
                    "dedicated funnelled comm-progress thread (ref: the "
                    "remote_dep_dequeue_main thread); default: workers "
                    "drain comm during idle cycles")
    params.reg_int("comm_thread_bind", -1,
                   "core to pin the comm thread to (ref: -C; -1 = unbound)")
    params.reg_bool("comm_failure_strict", False,
                    "treat ANY torn peer connection as a rank failure "
                    "(default: only when the peer owes data or is sent to)")
    # fault tolerance (ft/): proactive detection, injection, restart
    params.reg_string("ft_heartbeat_interval", "",
                      "seconds between heartbeat probes per peer (e.g. "
                      "0.05); empty/0 = proactive failure detection off")
    params.reg_string("ft_heartbeat_timeout", "",
                      "declare an established peer dead after this many "
                      "seconds of heartbeat silence (default: 8x the "
                      "interval); must exceed the longest un-pumped "
                      "progress stretch on in-process fabrics")
    params.reg_string("ft_detector_mode", "timeout",
                      "liveness judgment: timeout (fixed deadline) | phi "
                      "(phi-accrual-style: deadline scales with the "
                      "observed inter-arrival EWMA, floored at the "
                      "timeout)")
    params.reg_string("ft_inject", "",
                      "deterministic fault-injection spec, e.g. "
                      "\"kill:rank=1:after=3,drop:pct=2:seed=7\" "
                      "(ops: kill, taskfail, drop, dup, delay, failsend; "
                      "see ft/inject.py)")
    params.reg_string("ft_restart_policy", "",
                      "restart policy for ft.restart.run_with_restart: "
                      "\"abort\" or "
                      "\"restart:retries=N:backoff=S:every=K\"")
    params.reg_string("ft_elastic", "",
                      "elastic grid recovery (ft/elastic.py): \"shrink\" "
                      "(survivors of a rank loss agree on a reduced grid, "
                      "reshard the last snapshot onto it, and replay), "
                      "\"grow\" (fold announced joiners in at stage "
                      "boundaries), \"both\", or empty (default) for "
                      "today's fail-fast abort")
    params.reg_int("ft_elastic_grow_min", 1,
                   "minimum announced joiners worth a grid resize at a "
                   "stage boundary (grow mode)")
    params.reg_string("ft_elastic_timeout", "",
                      "membership-agreement deadline in seconds "
                      "(default 30); on expiry the run falls back to the "
                      "strict abort path with consistent snapshots")
    # multi-process deployment (tools/launch.py sets these per rank —
    # the mpiexec analog; ref: parsec_remote_dep_set_ctx runtime.h:221)
    params.reg_string("comm_transport", "",
                      "auto-wire a comm engine at init: \"tcp\" (endpoints "
                      "from comm_endpoints) or empty for none")
    params.reg_string("comm_endpoints", "",
                      "comma list of host:port control-plane endpoints, "
                      "one per rank, identical on every rank")
    params.reg_int("comm_rank", -1, "this process's rank in comm_endpoints")
    params.reg_string("jax_coordinator", "",
                      "host:port of the jax.distributed coordinator; set "
                      "on every rank to build one global device mesh "
                      "across processes (GSPMD over DCN/ICI)")
    params.reg_int("jax_num_processes", 0,
                   "process count for jax.distributed.initialize")
    params.reg_int("jax_process_id", -1,
                   "this process's id for jax.distributed.initialize")
    # multi-tenant persistent serving (serve/, ISSUE 18)
    params.reg_bool("serve", False,
                    "multi-tenant persistent serving (serve/): advertise "
                    "the \"sv\" HELLO capability so SessionServer "
                    "submission endpoints accept remote tenants, and pull "
                    "the obs_live monitor up for per-tenant SLO "
                    "attribution; off (default) constructs nothing and "
                    "keeps the wire bit-for-bit")
    params.reg_string("serve_admission", "reject",
                      "over-quota submission policy: \"reject\" (the "
                      "submission fails with AdmissionError / an error "
                      "reply) or \"queue\" (it parks on the tenant's "
                      "queue and launches when capacity frees)")
    params.reg_int("serve_max_tenants", 64,
                   "max named tenant sessions one SessionServer accepts")
    params.reg_int("serve_default_weight", 1,
                   "fair-share weight a tenant gets when open_tenant "
                   "declares none (>= 1; the deficit fairness boost "
                   "normalizes completed work by this weight)")
    params.reg_sizet("serve_default_quota_bytes", 0,
                     "Mempool byte quota a tenant gets when open_tenant "
                     "declares none (0 = unlimited)")
    params.reg_int("serve_latency_window", 512,
                   "per-tenant taskpool-latency samples kept for the "
                   "P99_LATENCY_US gauge and health snapshots")
    # device-plane transport + collective redistribution (xfer/, ISSUE 19)
    params.reg_bool("xfer_dplane", False,
                    "device-plane tile transport (xfer/): advertise the "
                    "\"dp\" HELLO capability and move bulk tile payloads "
                    "chip-to-chip over the transfer plane when both link "
                    "ends negotiated it; the session envelope still "
                    "carries the control half (header/ack) so replay and "
                    "flap semantics are unchanged. Off (default) keeps "
                    "the wire bit-for-bit")
    params.reg_bool("xfer_collective_redist", False,
                    "plan collections/redistribute as coalesced "
                    "alltoall-style collective rounds (xfer/plan.py) "
                    "instead of the per-tile GET storm, and switch the "
                    "wave collective lane to the two-level hierarchical "
                    "reduction (parallel/mesh.two_level_allreduce). Off "
                    "(default) constructs nothing and keeps the wire "
                    "bit-for-bit")
    params.reg_string("xfer_backend", "auto",
                      "device-plane transfer backend: \"auto\" (use "
                      "jax.experimental.transfer when the platform "
                      "provides it, else the in-process loopback), "
                      "\"native\" (require jax transfer), \"loopback\" "
                      "(force the socket loopback backend — what CI runs)")
    params.reg_int("xfer_group_size", 0,
                   "two-level collective group size (ranks per "
                   "intra-group psum before the quantized boundary hop); "
                   "0 = derive from the rank-mesh geometry, else 2")


register_core_params()
