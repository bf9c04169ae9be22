"""Direct coverage for the parallel/mesh.py shard_map seams (ISSUE 6
satellite): axis-name plumbing through ``shard_map_compat`` and the
``vary_on`` / ``match_vma`` VMA-promotion helpers.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from parsec_tpu.parallel import make_mesh, shard_map_compat
from parsec_tpu.parallel.mesh import match_vma, vary_on


def _mesh22():
    return make_mesh(sizes={"tp": 2, "sp": 2},
                     devices=jax.devices("cpu")[:4])


def test_axis_name_plumbing_psum_per_axis():
    """psum inside the compat wrapper must see the mesh's axis names
    and reduce over EXACTLY the named axis — 'tp' sums pairs of
    tp-shards, 'sp' sums pairs of sp-shards."""
    mesh = _mesh22()
    x = np.arange(16, dtype=np.float32).reshape(4, 4)

    def body_tp(xs):
        return jax.lax.psum(xs, "tp")

    # psum over tp leaves the value tp-replicated, so dim0 comes back
    # unsharded: each (2, 2) block summed with the other tp row's
    f = shard_map_compat(body_tp, mesh,
                         in_specs=P("tp", "sp"), out_specs=P(None, "sp"))
    got = np.asarray(f(jnp.asarray(x)))
    np.testing.assert_allclose(got, x[:2] + x[2:])

    def body_sp(xs):
        return jax.lax.psum(xs, "sp")

    g = shard_map_compat(body_sp, mesh,
                         in_specs=P("tp", "sp"), out_specs=P("tp", None))
    np.testing.assert_allclose(np.asarray(g(jnp.asarray(x))),
                               x[:, :2] + x[:, 2:])


def test_replicated_output_spec():
    """P() output must come out identical on every shard (a full
    reduction over both axes)."""
    mesh = _mesh22()
    x = np.arange(8, dtype=np.float32)

    def body(xs):
        return jax.lax.psum(xs.sum(), ("tp", "sp"))

    f = shard_map_compat(body, mesh,
                         in_specs=P(("tp", "sp")), out_specs=P())
    assert float(f(jnp.asarray(x))) == float(x.sum())


def test_vary_on_promotes_scan_carry():
    """A fresh-zeros scan carry is 'unvarying' under check_vma while
    the loop body makes it varying; vary_on must promote it so the
    scan's carry types match (the ring-attention/pipeline pattern)."""
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])
    x = np.arange(16, dtype=np.float32)

    def body(xs):
        acc0 = vary_on(jnp.zeros((), jnp.float32), ("sp",), like=xs)

        def step(acc, v):
            return acc + v, acc

        acc, _ = jax.lax.scan(step, acc0, xs)
        return jax.lax.psum(acc, "sp")

    f = shard_map_compat(body, mesh, in_specs=P("sp"), out_specs=P())
    assert float(f(jnp.asarray(x))) == float(x.sum())


def test_match_vma_promotes_to_reference():
    """match_vma must lift a constant to the reference's varying axes
    (and be the identity on values) so mixed carries scan cleanly."""
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])
    x = np.arange(8, dtype=np.float32).reshape(4, 2)

    def body(xs):
        m0 = match_vma(jnp.full((2,), -1.0, jnp.float32), xs)

        def step(m, row):
            return jnp.maximum(m, row), ()

        m, _ = jax.lax.scan(step, m0, xs)
        return jax.lax.pmax(m, "sp")

    f = shard_map_compat(body, mesh, in_specs=P("sp", None), out_specs=P())
    np.testing.assert_allclose(np.asarray(f(jnp.asarray(x))),
                               x.max(axis=0))


def test_match_vma_identity_outside_tracing():
    """Outside a shard_map trace there is no VMA to match: both helpers
    must be value-identity no-ops."""
    x = jnp.ones((3,))
    assert match_vma(x, x) is x
    np.testing.assert_allclose(np.asarray(vary_on(x, ())), np.asarray(x))


def test_grad_of_replicated_leaf_is_presummed():
    """The reason shard_map_compat insists on check_vma: jax.grad of a
    REPLICATED leaf through a psum'd forward must come out already
    summed over the axes its contributions were partial on."""
    mesh = make_mesh(sizes={"sp": 4}, devices=jax.devices("cpu")[:4])
    x = np.arange(4, dtype=np.float32) + 1.0

    def loss(w, xs):
        def body(w, xs):
            return jax.lax.psum((w * xs).sum(), "sp")
        f = shard_map_compat(body, mesh,
                             in_specs=(P(), P("sp")), out_specs=P())
        return f(w, xs)

    g = jax.grad(loss)(jnp.float32(2.0), jnp.asarray(x))
    # d/dw sum(w * x) = sum(x), gathered across every shard exactly once
    np.testing.assert_allclose(float(g), float(x.sum()), rtol=1e-6)
