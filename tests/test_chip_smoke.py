"""chip_smoke.py and the start-up rules around it, on the CPU.

What only a chip can show (that the smoke PASSES) is the driver's to
run; what the sandbox can show is that it cannot pass here, that its
rehearsal still drives every leg, that the compile cache lands where
the deployment says, and that ``init()`` refuses a missing backend
instead of running on the host.
"""
import json
import os
import subprocess
import sys

import pytest

import parsec_tpu
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
LEGS = ("main", "turbo", "stagec", "wave", "capture", "dgeqrf", "dtd",
        "host", "mesh", "ranks")


def _env(**over):
    """The suite's env minus what conftest pins for in-process tests
    (the smoke starts from ``params`` defaults)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PARSEC_MCA_") and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(over)
    return env


def _has_pass_line(stdout):
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("ok"):
            return True
    return False


def test_smoke_refuses_to_run_without_a_chip():
    p = subprocess.run([sys.executable, SMOKE], env=_env(), cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stdout and "'cpu'" in p.stdout, p.stdout[-800:]
    assert not _has_pass_line(p.stdout)


def test_smoke_rehearsal_runs_every_leg_and_cannot_pass():
    """Four virtual devices, so the four-chip legs rehearse too."""
    p = subprocess.run(
        [sys.executable, SMOKE, "--rehearse"], cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert all(ln.startswith("REHEARSAL ")
               for ln in p.stdout.splitlines()), p.stdout
    for leg in LEGS:
        assert f"REHEARSAL {leg}: ok in" in p.stdout, (leg, p.stdout[-4000:])
    assert "N=512" in p.stdout
    assert not _has_pass_line(p.stdout)


_CACHE_PROBE = ("import parsec_tpu, jax; "
                "print(jax.config.jax_compilation_cache_dir); "
                "print(jax.config.jax_persistent_cache_min_compile_time_secs)")


def _cache_probe(tmp_path, **over):
    env = _env(PYTHONPATH=ROOT)
    del env["JAX_PLATFORMS"]       # importing touches no backend
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(over)
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.split()


def test_compile_cache_defaults_to_the_checkout_from_any_cwd(tmp_path):
    path, floor = _cache_probe(tmp_path)
    assert path == os.path.join(ROOT, ".jax_cache")
    assert float(floor) == 0.0


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    path, floor = _cache_probe(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert path == placed          # JAX's own reading of its variable
    assert float(floor) == 0.0
    assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))


def test_compile_cache_stays_off_for_a_process_held_to_the_host(tmp_path):
    path, floor = _cache_probe(tmp_path, JAX_PLATFORMS="cpu")
    assert path == "None" and float(floor) == 1.0


def test_init_raises_when_the_pinned_platform_is_absent():
    with params.cmdline_override("device_tpu_platform", "tpu"):
        with pytest.raises(RuntimeError, match="could not be initialized"):
            parsec_tpu.init(nb_cores=1)
        # the explicit way to run host-only still works
        ctx = parsec_tpu.init(nb_cores=1, enable_tpu=False)
        try:
            assert [d.device_type for d in ctx.devices] == ["cpu"]
        finally:
            ctx.fini()

