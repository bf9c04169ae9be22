#!/usr/bin/env python
"""parsec_tpu process launcher — the mpiexec analog.

Spawns N SPMD rank processes of a user program, wiring each one's comm
engine via PARSEC_MCA_* env vars (the reference hands each process its
communicator through mpiexec + MPI_Init; here the launcher allocates the
control-plane endpoints and each rank's Context auto-builds a
TCPCommEngine + RemoteDepEngine at init, runtime/context.py
_comm_from_params). Ref: parsec/parsec_mpi_funnelled.c:245-365 (the
transport this replaces), SURVEY.md §5.8.

Usage:
  python tools/launch.py -n N [options] prog.py [prog args...]

Options:
  -n N                 number of ranks (default 2)
  --jax-distributed    also start a jax.distributed coordinator so the
                       ranks form ONE global jax device mesh (GSPMD
                       across processes); rank 0 hosts the coordinator
  --host H             bind host (default 127.0.0.1)
  --timeout S          per-rank wall clock limit (default 3600)
  --env K=V            extra env var for every rank (repeatable)

Multi-host (the thing mpiexec exists to do):
  --hosts H1,H2,...    place ranks round-robin on these hosts; each
                       rank's endpoint binds ITS host's real interface
                       and non-local ranks are spawned through --ssh
                       (`ssh Hk 'cd WORKDIR && env VARS python prog'`).
                       An entry is NAME[:BINDADDR] — ssh to NAME, bind
                       the endpoint on BINDADDR (management vs data
                       plane). Hosts named localhost/127.* spawn
                       directly.
  --ssh CMD            remote-spawn command (default "ssh"; any agent
                       that accepts `CMD host shell-command` works)
  --python EXE         remote interpreter (default: this one)
  --workdir DIR        remote working directory + PYTHONPATH (default:
                       this repo's root — assume a shared filesystem or
                       an identical checkout, like any MPI deployment)
  --port-base P        first control-plane port for --hosts runs
                       (default 28900; rank r listens on P+r, the jax
                       coordinator on P+N)

The v5p-style deployment recipe lives in docs/guide.md ("Multi-host
deployment").

One process per chip: a TPU chip belongs to one process at a time. A
single local rank owns every chip of its host (the one-process-per-host
pod layout). When SEVERAL ranks share this host and it has chips, each
is given its OWN chip through libtpu's visibility variables (local rank
r sees chip r alone), and a launch with more local ranks than chips is
refused — it never has two ranks open one chip. Ranks held to the host
(JAX_PLATFORMS=cpu in the environment or via --env) need no chip and
are not bound. The launcher itself never initialises JAX: a parent that
touched it would hold the chips its ranks need.

Each rank's stdout/stderr is streamed line-by-line with a "[r]" prefix.
Exit status: 0 when every rank exits 0; otherwise the first non-zero
rank's status (remaining ranks are killed — fail fast, like mpiexec).
"""
import argparse
import glob
import os
import shlex
import signal
import subprocess
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_LOCAL_NAMES = ("localhost", "127.", "::1")


def _is_local(host: str) -> bool:
    return host == "" or host == "::1" or \
        any(host == n or host.startswith(n) for n in _LOCAL_NAMES)


def local_chip_count() -> int:
    """TPU chips of this host, counted from their device nodes
    (/dev/accel* on older generations, /dev/vfio/<n> from v5e on) —
    without JAX, which would open them."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def chip_binding(local_rank: int) -> dict:
    """libtpu's visibility variables for a process that owns exactly
    one chip: it sees chip ``local_rank`` as a 1x1x1 topology of its
    own (no ICI to its neighbours; ranks talk over the comm engine)."""
    return {"TPU_VISIBLE_CHIPS": str(local_rank),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="launch.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", type=int, default=2, dest="nranks")
    ap.add_argument("--jax-distributed", action="store_true")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--env", action="append", default=[])
    ap.add_argument("--hosts", default=None)
    ap.add_argument("--ssh", default="ssh")
    ap.add_argument("--python", default=sys.executable)
    ap.add_argument("--workdir", default=ROOT)
    ap.add_argument("--port-base", type=int, default=28900)
    ap.add_argument("prog")
    ap.add_argument("prog_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    from parsec_tpu.comm.tcp import free_ports

    n = args.nranks
    if args.hosts:
        # each entry is NAME[:BINDADDR]: NAME is the --ssh target (the
        # management hostname), BINDADDR the data-plane interface the
        # rank's endpoint binds/advertises (defaults to NAME)
        hosts = []
        for h in args.hosts.split(","):
            h = h.strip()
            if h:
                name, _, bind = h.partition(":")
                hosts.append((name, bind or name))
        if not hosts:
            ap.error("--hosts: empty host list")
        host_of = [hosts[r % len(hosts)][0] for r in range(n)]
        bind_of = [hosts[r % len(hosts)][1] for r in range(n)]
        # remote hosts can't join a local free-port probe: fixed
        # port-base layout, unique per rank even when hosts repeat
        ports = [args.port_base + r for r in range(n + 1)]
    else:
        host_of = [args.host] * n
        bind_of = host_of
        ports = free_ports(n + (1 if args.jax_distributed else 0))
    endpoints = ",".join(f"{bind_of[r]}:{ports[r]}" for r in range(n))

    # vars the launcher wires (carried to remote ranks over --ssh; the
    # full local environ only reaches directly-spawned local ranks)
    wired = {}
    for kv in args.env:
        k, _, v = kv.partition("=")
        wired[k] = v
    wired["PARSEC_MCA_comm_transport"] = "tcp"
    wired["PARSEC_MCA_comm_endpoints"] = endpoints
    if args.jax_distributed:
        wired["PARSEC_MCA_jax_coordinator"] = f"{bind_of[0]}:{ports[n]}"
        wired["PARSEC_MCA_jax_num_processes"] = str(n)
    base_env = dict(os.environ)
    base_env.update(wired)

    # one process per chip (module docstring): bind, or refuse
    local_ranks = [r for r in range(n)
                   if not (args.hosts and not _is_local(host_of[r]))]
    chips = local_chip_count()
    bind_chips = (len(local_ranks) > 1 and chips > 0
                  and base_env.get("JAX_PLATFORMS") != "cpu")
    if bind_chips and len(local_ranks) > chips:
        sys.stderr.write(
            f"launch.py: {len(local_ranks)} local ranks but this host "
            f"has {chips} TPU chip(s), and a chip belongs to one "
            f"process at a time. Launch at most {chips} rank(s) here, "
            f"spread them with --hosts, run the ranks in ONE process "
            f"(in-process ranks or device_mesh_shape drive all chips), "
            f"or hold them to the host with --env JAX_PLATFORMS=cpu.\n")
        return 2
    if bind_chips and args.jax_distributed:
        sys.stderr.write(
            "launch.py: --jax-distributed with several ranks on one "
            "host with TPU chips is not supported: each rank is bound "
            "to a chip of its own (a 1x1x1 topology), which cannot "
            "join ONE global mesh. Run one rank per host (it owns all "
            "of the host's chips), or use --env JAX_PLATFORMS=cpu for "
            "the CPU substrate.\n")
        return 2

    procs = []
    for r in range(n):
        rank_over = {"PARSEC_MCA_comm_rank": str(r)}
        if args.jax_distributed:
            rank_over["PARSEC_MCA_jax_process_id"] = str(r)
        if args.hosts and not _is_local(host_of[r]):
            over = dict(wired)
            over.update(rank_over)
            over.setdefault("PYTHONPATH", args.workdir)
            parts = ["cd", shlex.quote(args.workdir), "&&", "env"]
            parts += [f"{k}={shlex.quote(v)}"
                      for k, v in sorted(over.items())]
            # resolve prog against the REMOTE workdir (the local
            # checkout path means nothing on the other machine);
            # absolute paths are taken as-is
            rprog = args.prog if os.path.isabs(args.prog) else \
                os.path.join(args.workdir, args.prog)
            parts += [shlex.quote(args.python), shlex.quote(rprog)]
            parts += [shlex.quote(a) for a in args.prog_args]
            cmd = shlex.split(args.ssh) + [host_of[r], " ".join(parts)]
            procs.append(subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        else:
            env = dict(base_env)
            env.update(rank_over)
            if bind_chips:
                env.update(chip_binding(local_ranks.index(r)))
            procs.append(subprocess.Popen(
                [sys.executable, args.prog] + args.prog_args,
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))

    def pump(r, stream):
        for line in stream:
            sys.stdout.write(f"[{r}] {line}")
            sys.stdout.flush()

    pumps = [threading.Thread(target=pump, args=(r, p.stdout), daemon=True)
             for r, p in enumerate(procs)]
    for t in pumps:
        t.start()

    rc = 0
    try:
        for r, p in enumerate(procs):
            try:
                p.wait(timeout=args.timeout)
            except subprocess.TimeoutExpired:
                sys.stderr.write(f"launch.py: rank {r} exceeded "
                                 f"{args.timeout}s; killing all\n")
                rc = rc or 124
                break
            if p.returncode != 0 and rc == 0:
                sys.stderr.write(f"launch.py: rank {r} exited "
                                 f"{p.returncode}; killing the rest\n")
                rc = p.returncode
                break
    except KeyboardInterrupt:
        rc = 130
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for t in pumps:
            t.join(timeout=2)
    if rc == 0 and any(p.returncode != 0 for p in procs):
        rc = next(p.returncode for p in procs if p.returncode != 0)
    return rc


if __name__ == "__main__":
    sys.exit(main())
