"""Gradient correctness of the 5-axis-parallel training step.

The sharded loss runs under shard_map with manual collectives; replicated
leaves get their gradients psum'd over sync_axes. This test checks the
resulting GLOBAL gradients numerically against plain single-device
autodiff of an independently-written reference implementation of the same
math — the only way to catch over-counting across axes where compute is
redundant (e.g. the whole forward across ep for a dense model, the
residual stream across tp).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from parsec_tpu.models import TransformerConfig, init_params, param_specs
from parsec_tpu.models.transformer import loss_shard
from parsec_tpu.parallel import make_mesh, shard_map_compat, sync_axes
from parsec_tpu.parallel.moe import load_balance_loss
from parsec_tpu.parallel.ring_attention import local_attention


def _rmsnorm(x, g):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * g


def ref_loss(cfg: TransformerConfig, params, tokens, labels,
             aux_blocks=(1, 1)):
    """Single-device reference of the flagship model's loss.

    aux_blocks=(dp, sp): the sharded Switch-aux is estimated per
    (batch-shard, sequence-shard) token block then averaged; the
    reference reproduces that estimator (it differs from the whole-batch
    one because the load-balance loss is nonlinear in token statistics).
    """
    x = params["embed"][tokens] + params["pos"][jnp.arange(cfg.seq_len)][None]
    x = x.astype(cfg.dtype)
    st = params["stages"]
    aux_total = jnp.zeros((), jnp.float32)
    for s in range(cfg.n_stages):
        for l in range(cfg.layers_per_stage):
            h = _rmsnorm(x, st["ln1"][s, l])
            qkv = jnp.einsum("btd,dchn->bcthn", h, st["wqkv"][s, l],
                             preferred_element_type=jnp.float32).astype(x.dtype)
            q = qkv[:, 0].transpose(0, 2, 1, 3)
            k = qkv[:, 1].transpose(0, 2, 1, 3)
            v = qkv[:, 2].transpose(0, 2, 1, 3)
            a = local_attention(q, k, v, causal=True)
            o = jnp.einsum("bhtd,hdD->btD", a, st["wo"][s, l],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            x = x + o
            h2 = _rmsnorm(x, st["ln2"][s, l])
            if cfg.n_experts:
                gl = jnp.einsum("btd,de->bte", h2, st["gate"][s, l])
                probs = jax.nn.softmax(gl, axis=-1)
                if cfg.moe_top_k < cfg.n_experts:
                    thresh = jax.lax.top_k(probs, cfg.moe_top_k)[0][..., -1:]
                    m = probs >= thresh
                    probs = probs * m
                    probs = probs / (probs.sum(-1, keepdims=True) + 1e-9)
                he = jnp.einsum("...d,edf->...ef", h2, st["w1e"][s, l],
                                preferred_element_type=jnp.float32)
                he = jax.nn.gelu(he)
                ye = jnp.einsum("...ef,efd->...ed", he, st["w2e"][s, l],
                                preferred_element_type=jnp.float32)
                f = jnp.einsum("...ed,...e->...d", ye,
                               probs.astype(ye.dtype)).astype(x.dtype)
                dp_b, sp_b = aux_blocks
                B, T, E = gl.shape
                blocks = gl.reshape(dp_b, B // dp_b, sp_b, T // sp_b, E)
                aux_blk = jnp.mean(jnp.stack([
                    load_balance_loss(blocks[d, :, s])
                    for d in range(dp_b) for s in range(sp_b)]))
                aux_total = aux_total + aux_blk
            else:
                u = jnp.einsum("btd,df->btf", h2, st["w1"][s, l],
                               preferred_element_type=jnp.float32)
                u = jax.nn.gelu(u).astype(x.dtype)
                f = jnp.einsum("btf,fD->btD", u, st["w2"][s, l],
                               preferred_element_type=jnp.float32).astype(x.dtype)
            x = x + f
    y = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("btd,vd->btv", y.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    if cfg.n_experts:
        loss = loss + cfg.aux_loss_weight * aux_total / cfg.n_layers
    return loss


def _sharded_loss_and_grads(cfg, mesh, params, tokens, labels):
    pspecs = param_specs(cfg)

    def shard(p, t, y):
        # VMA-checked shard_map: grads of replicated leaves come out
        # already reduced over the correct axes (no manual sync psum)
        loss, grads = jax.value_and_grad(
            lambda pp: loss_shard(cfg, pp, t, y))(p)
        return loss, grads

    fn = shard_map_compat(shard, mesh,
                          in_specs=(pspecs, P("dp", "sp"), P("dp", "sp")),
                          out_specs=(P(), pspecs))
    return fn(params, tokens, labels)


@pytest.mark.parametrize("case", ["dense_ep2_tp2", "moe_ep2", "pp2_sp2"])
def test_sharded_grads_match_reference(case):
    if case == "dense_ep2_tp2":
        # the killer config: ep is completely unused by a dense model, and
        # the residual stream is redundant across tp
        sizes = {"dp": 2, "tp": 2, "ep": 2}
        cfg = TransformerConfig(vocab=17, d_model=8, n_heads=4, d_head=4,
                                d_ff=8, seq_len=8, batch=4, n_experts=0)
    elif case == "moe_ep2":
        sizes = {"dp": 2, "tp": 2, "ep": 2}
        cfg = TransformerConfig(vocab=17, d_model=8, n_heads=4, d_head=4,
                                d_ff=8, seq_len=8, batch=4, n_experts=4,
                                moe_top_k=2)
    else:
        sizes = {"pp": 2, "sp": 2, "ep": 2}
        cfg = TransformerConfig(vocab=17, d_model=8, n_heads=4, d_head=4,
                                d_ff=8, seq_len=8, batch=4, n_experts=0,
                                n_stages=2, layers_per_stage=1, n_micro=2)
    devs = jax.devices("cpu")
    mesh = make_mesh(sizes=sizes, devices=devs[:int(np.prod(list(sizes.values())))])

    params = init_params(cfg, seed=3)
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, cfg.vocab, size=(cfg.batch, cfg.seq_len)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1).astype(np.int32)

    loss_s, grads_s = _sharded_loss_and_grads(cfg, mesh, params, tokens, labels)
    blocks = (sizes.get("dp", 1), sizes.get("sp", 1))
    ref = jax.jit(jax.value_and_grad(
        lambda p: ref_loss(cfg, p, jnp.asarray(tokens), jnp.asarray(labels),
                           aux_blocks=blocks)))
    loss_r, grads_r = ref(params)

    np.testing.assert_allclose(float(loss_s), float(loss_r), rtol=1e-5)
    flat_s = jax.tree.leaves_with_path(grads_s)
    flat_r = dict(jax.tree.leaves_with_path(grads_r))
    assert flat_s and len(flat_s) == len(flat_r)
    for path, g in flat_s:
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_r[path]), rtol=5e-4, atol=5e-5,
            err_msg=f"gradient mismatch at {jax.tree_util.keystr(path)}")
