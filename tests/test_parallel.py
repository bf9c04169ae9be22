"""Mesh-parallel primitive tests on the virtual 8-device CPU mesh:
ring attention == local attention, Ulysses == local attention, GPipe ==
sequential stages, expert-parallel MoE == single-shard MoE, and the full
5-axis training step reduces the loss.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from parsec_tpu.parallel import (make_mesh, shard_map_compat, sync_axes,
                                 gpipe, last_stage_value, local_attention,
                                 moe_ffn, ring_attention, ulysses_attention)


def _qkv(B=2, H=4, T=16, Dh=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, T, Dh)), dtype=jnp.float32)
    return mk(), mk(), mk()


def test_sync_axes():
    assert sync_axes(P("pp", None, "tp")) == ("dp", "sp", "ep")
    assert sync_axes(P()) == ("dp", "pp", "tp", "sp", "ep")
    assert sync_axes(P(("dp", "tp"))) == ("pp", "sp", "ep")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_local(causal):
    q, k, v = _qkv()
    ref = local_attention(q, k, v, causal=causal)
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])
    f = shard_map_compat(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_attention_matches_jnp_ring(causal):
    """The Pallas-local-block ring (stats-merge across shards) must agree
    with the jnp online-softmax ring, forward AND gradients."""
    q, k, v = _qkv(T=32)
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])

    def run(use_pallas):
        f = shard_map_compat(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal,
                                           use_pallas=use_pallas),
            mesh, in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"))

        def loss(q, k, v):
            return (f(q, k, v) * jnp.cos(jnp.arange(q.shape[-1]))).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return f(q, k, v), g

    out_f, g_f = run(True)
    out_j, g_j = run(False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_j),
                               rtol=2e-4, atol=2e-4)
    for a, b in zip(g_f, g_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    # and against the single-shard reference
    ref = local_attention(q, k, v, causal=causal, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_matches_local():
    q, k, v = _qkv()
    ref = local_attention(q, k, v, causal=True)
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])
    f = shard_map_compat(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh, in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gpipe_matches_sequential():
    """4 stages, each multiplies by its own matrix: pipeline result must
    equal the sequential composition."""
    S, M, mb, D = 4, 3, 2, 8
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.normal(size=(S, D, D)) / np.sqrt(D), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, mb, D)), jnp.float32)
    ref = x
    for s in range(S):
        ref = jnp.einsum("mbd,dk->mbk", ref, Ws[s])

    mesh = make_mesh(sizes={"pp": 4}, devices=jax.devices("cpu")[:4])

    def run(ws_local, xm):
        def stage_fn(w, a):
            return jnp.einsum("bd,dk->bk", a, w[0])
        out = gpipe(stage_fn, ws_local, xm, "pp")
        return last_stage_value(out, "pp")

    f = shard_map_compat(run, mesh, in_specs=(P("pp"), P()), out_specs=P())
    out = f(Ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_gpipe_gradient_flows():
    S, M, mb, D = 2, 2, 2, 4
    rng = np.random.RandomState(2)
    Ws = jnp.asarray(rng.normal(size=(S, D, D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, mb, D)), jnp.float32)
    mesh = make_mesh(sizes={"pp": 2}, devices=jax.devices("cpu")[:2])

    def loss_fn(ws_local, xm):
        def stage_fn(w, a):
            return jnp.tanh(jnp.einsum("bd,dk->bk", a, w[0]))
        out = gpipe(stage_fn, ws_local, xm, "pp")
        return last_stage_value(jnp.sum(out ** 2), "pp")

    def grads(ws_local, xm):
        return jax.grad(loss_fn)(ws_local, xm)

    f = shard_map_compat(grads, mesh, in_specs=(P("pp"), P()),
                         out_specs=P("pp"))
    g = f(Ws, x)
    assert np.asarray(g).shape == (S, D, D)
    assert np.all(np.isfinite(np.asarray(g)))
    assert np.abs(np.asarray(g)).max() > 0


def test_moe_expert_parallel_matches_single():
    rng = np.random.RandomState(3)
    B, T, D, F, E = 2, 4, 8, 16, 4
    x = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, D, F)) / np.sqrt(D), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(E, F, D)) / np.sqrt(F), jnp.float32)

    mesh1 = make_mesh(sizes={"ep": 1}, devices=jax.devices("cpu")[:1])
    ref = shard_map_compat(
        lambda x, g, a, b: moe_ffn(x, g, a, b, "ep", top_k=2),
        mesh1, in_specs=(P(), P(), P("ep"), P("ep")), out_specs=P())(
            x, gate, w1, w2)

    mesh4 = make_mesh(sizes={"ep": 4}, devices=jax.devices("cpu")[:4])
    out = shard_map_compat(
        lambda x, g, a, b: moe_ffn(x, g, a, b, "ep", top_k=2),
        mesh4, in_specs=(P(), P(), P("ep"), P("ep")), out_specs=P())(
            x, gate, w1, w2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_train_step_reduces_loss():
    """Full 5-axis training step on the 8-device mesh: loss must drop."""
    from parsec_tpu.models import (TransformerConfig, adam_init, init_params,
                                   make_train_step)
    mesh = make_mesh(8)
    sz = dict(zip(mesh.axis_names, mesh.devices.shape))
    cfg = TransformerConfig(
        vocab=32, d_model=16, n_heads=2 * sz["tp"] * sz["sp"], d_head=4,
        n_stages=sz["pp"], layers_per_stage=1, d_ff=4 * sz["tp"],
        n_experts=2 * sz["ep"], seq_len=4 * sz["sp"],
        batch=2 * sz["dp"] * 2, n_micro=2)
    params = init_params(cfg)
    state = adam_init(params)
    step = make_train_step(cfg, mesh, lr=5e-3)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, size=(cfg.batch, cfg.seq_len)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1).astype(np.int32)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, tokens, labels)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_train_step_flash_remat_matches_local():
    """attention='flash' (Pallas) + remat must produce the same step as
    'local' attention without remat — same loss trajectory (single-shard
    sequence: flash and local compute identical attention)."""
    from parsec_tpu.models import (TransformerConfig, adam_init, init_params,
                                   make_train_step)
    mesh = make_mesh(1)
    base = dict(vocab=64, d_model=32, n_heads=4, d_head=8,
                n_stages=1, layers_per_stage=2, d_ff=64,
                seq_len=32, batch=2, n_micro=1)
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, 64, size=(2, 32)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1).astype(np.int32)

    def run(**kw):
        cfg = TransformerConfig(**base, **kw)
        params = init_params(cfg)
        state = adam_init(params)
        step = make_train_step(cfg, mesh, lr=5e-3)
        out = []
        for _ in range(3):
            params, state, loss = step(params, state, tokens, labels)
            out.append(float(loss))
        return out

    ref = run(attention="local", remat=False)
    got = run(attention="flash", remat=True)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
