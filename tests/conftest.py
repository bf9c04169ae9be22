"""Test configuration: force a virtual 8-device CPU mesh before jax loads.

Multi-chip TPU hardware is not available in CI; sharding and multi-device
semantics are validated on XLA's host platform with 8 virtual devices
(the reference's analog: oversubscribed mpiexec on one node, SURVEY.md §4).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the device module attaches the virtual CPU devices as its accelerators
os.environ.setdefault("PARSEC_MCA_device_tpu_platform", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 quick run (-m 'not slow')")

# full matmul precision so references match
import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


def assert_ulp_close(got, want, ulps=8):
    """``got`` and ``want`` are the same arithmetic run as two XLA
    programs of different SHAPE (a fused stage against per-task
    dispatch, one chip against a mesh).  XLA fuses and contracts each
    program on its own, so the two agree to rounding and not to the
    bit, on the CPU backend as on the chip (jax 0.9.0: 668 of 16,384
    elements of a 128/32 f32 dpotrf differ, by at most 1.9e-9).  The
    bound is a few ulp of the result's largest entry: a wrong tile, a
    stale read or a dropped update is off by orders of magnitude more.
    Bit-identity stays the contract where the bytes are the same
    program's or the wire's (replay, codecs, knob-unset differentials).
    """
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, \
        (got.shape, got.dtype, want.shape, want.dtype)
    # x64 is off: the device computes float64 tiles in float32
    eps = max(np.finfo(want.dtype).eps, np.finfo(np.float32).eps)
    tol = ulps * eps * np.abs(want).max()
    err = np.abs(got.astype(np.float64) - want.astype(np.float64)).max()
    assert err <= tol, (
        f"max |got - want| = {err:.3e} > {ulps} ulp of max|want| "
        f"= {tol:.3e}")


def spmd(nb_ranks, fn, timeout=120, fabric=None):
    """Run fn(rank, fabric) on one thread per rank over an in-process
    fabric; propagate exceptions. Delegates to the canonical harness
    (parsec_tpu/utils/spmd.py)."""
    from parsec_tpu.utils.spmd import spmd_threads

    return spmd_threads(nb_ranks, fn, timeout=timeout, fabric=fabric)


def spmd_tcp(nb_ranks, fn, overrides=None):
    """Run fn(rank, engine) on one thread per rank, each rank on its own
    loopback ``TCPCommEngine``, with the MCA ``overrides`` in force from
    engine construction to the last rank's return; the per-rank
    results.  The rank function finalizes what it builds on the engine
    (``Context.fini``)."""
    import concurrent.futures as cf
    from contextlib import ExitStack

    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.utils.params import params

    eps = [("127.0.0.1", p) for p in free_ports(nb_ranks)]
    with ExitStack() as st:
        for k, v in (overrides or {}).items():
            st.enter_context(params.cmdline_override(k, v))
        with cf.ThreadPoolExecutor(nb_ranks) as ex:
            return list(ex.map(
                lambda r: fn(r, TCPCommEngine(r, eps)), range(nb_ranks)))


@pytest.fixture
def ctx():
    import parsec_tpu
    c = parsec_tpu.init(nb_cores=2)
    yield c
    c.fini()


@pytest.fixture
def ctx4():
    import parsec_tpu
    c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


@pytest.fixture
def no_programs(monkeypatch):
    """A process that has built no stacked program yet: the device
    module's process-wide program cache (devices/batching.py), emptied
    for one test."""
    from parsec_tpu.devices import batching
    monkeypatch.setattr(batching, "_shared_cache", {})
    monkeypatch.setattr(batching, "_untraceable", set())


@pytest.fixture
def call_sizes(monkeypatch):
    """Tasks per stacked call, in dispatch order, of every device of
    the process (``batches`` and ``batched_tasks`` give their count and
    sum; this gives each)."""
    from parsec_tpu.devices.tpu import JaxDevice
    sizes = []
    stacked = JaxDevice._dispatch_stacked

    def recording(self, es, spec, static, shapes, donate, chunk):
        sizes.append(len(chunk))
        return stacked(self, es, spec, static, shapes, donate, chunk)

    monkeypatch.setattr(JaxDevice, "_dispatch_stacked", recording)
    return sizes
