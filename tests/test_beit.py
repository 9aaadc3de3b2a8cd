"""BEiT through ``PrivacyEngine`` at a small size, against plain references.

A 2-layer BEiT (d 64, 4 heads, 32 px images in patches of 8: 17 tokens, a
4x4 window, a 52-row relative position table per layer) with seeded
weights: each clipping mode, and ``mixed_ghost`` with every matmul tap
forced onto one branch, gives the per-sample norms and the clipped sum of
``vmap(grad)`` at ``highest`` precision; the table taps always instantiate; the biased flash op matches
softmax(Q K^T s + bias) V and its gradient; the auditor finds every
parameter behind its tap.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit_loss_fn
from repro.configs.paper_native import BEIT_LARGE
from repro.core.clipping import discover_meta
from repro.core.decision import decide
from repro.core.engine import PrivacyEngine
from repro.core.taps import Ctx
from repro.kernels.flash_attention.ops import flash_attention
from repro.models.vit import BEiT
from repro.nn.attention import relative_position_index

from helpers import forced_plan

SMALL = dataclasses.replace(
    BEIT_LARGE, n_layers=2, d_model=64, n_heads=4, n_kv=4, d_ff=256,
    dtype="float32", layer_scale=0.5,  # 1e-5 would hide the blocks' gradients
)
BATCH = 3


def _model():
    return BEiT(SMALL, image_size=32, patch=8, n_classes=10)


def _params(model):
    return model.init(jax.random.PRNGKey(0))


def _batch(n=BATCH):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    return {
        "image": jax.random.normal(k1, (n, 32, 32, 3), jnp.float32),
        "label": jax.random.randint(k2, (n,), 0, 10),
        "mask": jnp.ones((n,), jnp.float32),
    }


def _oracle(model, params, batch, clip_norm):
    """Per-sample norms and the clipped sum from vmap(grad), f32 at highest."""
    def one(p, image, label):
        ex = {"image": image[None], "label": label[None], "mask": jnp.ones((1,))}
        return model.loss_with_ctx(p, ex, Ctx.disabled())[0]

    with jax.default_matmul_precision("highest"):
        grads = jax.vmap(jax.grad(one), in_axes=(None, 0, 0))(
            params, batch["image"], batch["label"])
        leaves = jax.tree_util.tree_leaves(grads)
        norms = jnp.sqrt(sum(jnp.sum(jnp.square(g.reshape(g.shape[0], -1)), axis=1)
                             for g in leaves))
        c = jnp.minimum(clip_norm / norms, 1.0)
        summed = jax.tree_util.tree_map(lambda g: jnp.tensordot(c, g, axes=1), grads)
    return norms, summed


def test_small_beit_layout():
    model = _model()
    params = _params(model)
    table = params["layers"]["attn"]["relative_position_bias_table"]
    assert table.shape == (2, 52, 4)
    assert relative_position_index((4, 4)).shape == (17, 17)
    assert "pos_embed" not in params and params["cls_token"].shape == (1, 1, 64)
    assert "b" not in params["layers"]["attn"]["k"]  # q and v biases only
    logits = model.logits(params, _batch()["image"], Ctx.disabled())
    assert logits.shape == (BATCH, 10)


@pytest.mark.parametrize("mode,force", [
    ("mixed_ghost", None), ("mixed_ghost", "ghost"), ("mixed_ghost", "instantiate"),
    ("bk_mixed", None),
], ids=["mixed_ghost", "ghost", "instantiate", "bk_mixed"])
def test_engine_matches_vmap_grad(mode, force):
    """``force`` runs ``mixed_ghost`` under a plan that puts every matmul tap
    on one branch: the paper's ghost-only and instantiate-only algorithms."""
    model, batch = _model(), _batch()
    params = _params(model)
    norms0, _ = _oracle(model, params, batch, 1.0)
    clip_norm = float(jnp.median(norms0))  # some samples clipped, some not
    ref_norms, ref_sum = _oracle(model, params, batch, clip_norm)
    plan = forced_plan(model.loss_with_ctx, params, batch, force) if force else None
    engine = PrivacyEngine(
        loss_with_ctx=model.loss_with_ctx, batch_size=BATCH, sample_size=1000, steps=10,
        max_grad_norm=clip_norm, noise_multiplier=1.0, mode=mode, plan=plan,
    )
    with jax.default_matmul_precision("highest"):
        _, got_sum, aux = jax.jit(engine.clipped_grad_fn())(params, batch)
    np.testing.assert_allclose(aux["per_sample_norms"], ref_norms, rtol=2e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_sum)
    for (path, want), got in zip(flat_ref, jax.tree_util.tree_leaves(got_sum)):
        scale = max(float(jnp.linalg.norm(want)), 1e-6)
        err = float(jnp.linalg.norm(got - want)) / scale
        assert err < 5e-5, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("override", [None, "ghost"])
@pytest.mark.parametrize("mode", ["mixed_ghost", "bk_mixed"])
def test_table_taps_instantiate(mode, override):
    model = _model()
    meta = discover_meta(model.loss_with_ctx, _params(model), _batch())
    tables = {n: m for n, m in meta.items() if m.kind == "table"}
    assert list(tables) == ["layers/attn/rel_pos"]
    m = tables["layers/attn/rel_pos"]
    assert (m.T, m.D, m.p, m.stack_dims) == (17 * 17, 52, 4, (2,))
    assert m.param_path == "layers/attn/relative_position_bias_table"
    assert decide(m, mode=mode, override=override) == "instantiate"


def test_table_tap_norm_is_the_table_gradient_norm():
    """The table's share of each per-sample norm is small next to the head's,
    so it is checked on its own: the segment sum of the tap's cotangent
    against vmap(grad) of the table leaf."""
    from repro.core import ghost
    from repro.core.taps import make_zero_taps

    model, batch = _model(), _batch()
    params = _params(model)
    meta = discover_meta(model.loss_with_ctx, params, batch)
    name = "layers/attn/rel_pos"

    def losses(p, taps):
        return model.loss_with_ctx(p, batch, Ctx(taps=taps, meta={}))

    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(losses, params, make_zero_taps(meta))
        _, gs = pull(jnp.ones((BATCH,)))
        got = ghost.tap_norm_sq(meta[name], None, gs[name])

        def one(p, image, label):
            ex = {"image": image[None], "label": label[None], "mask": jnp.ones((1,))}
            return model.loss_with_ctx(p, ex, Ctx.disabled())[0]

        grads = jax.vmap(jax.grad(one), in_axes=(None, 0, 0))(
            params, batch["image"], batch["label"])
    table = grads["layers"]["attn"]["relative_position_bias_table"]  # (B, L, R, H)
    want = jnp.sum(jnp.square(table), axis=(1, 2, 3))
    assert float(jnp.min(want)) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5)


def _softmax_attention(q, k, v, bias):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5 + bias
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("seq,block,bias_shape", [
    (17, 512, (2, 4, 17, 17)),  # one block: the custom VJP
    (16, 8, (2, 4, 16, 16)),  # 2 x 2 blocks: the custom VJP's tiling
    (17, 8, (2, 4, 17, 17)),  # padded: differentiated through the scans
    (17, 512, (4, 17, 17)),  # shared by the batch: its cotangent sums over it
])
def test_biased_flash_matches_softmax(seq, block, bias_shape):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k, v = (jax.random.normal(kk, (2, seq, 4, 8)) for kk in ks[:3])
    bias = jax.random.normal(ks[3], bias_shape)
    do = jax.random.normal(ks[4], (2, seq, 4, 8))

    def flash(q, k, v, bias):
        return flash_attention(q, k, v, causal=False, bias=bias, block_q=block, block_kv=block)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(flash(q, k, v, bias), _softmax_attention(q, k, v, bias),
                                   atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), argnums=(0, 1, 2, 3))(q, k, v, bias)
        want = jax.grad(lambda *a: jnp.sum(_softmax_attention(*a) * do),
                        argnums=(0, 1, 2, 3))(q, k, v, bias)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-6)


def test_audit_finds_every_parameter_behind_its_tap():
    model = _model()
    findings = audit_loss_fn(model.loss_with_ctx, _params(model), _batch(), arch="beit-small")
    codes = {f.code for f in findings}
    assert not codes & {"uncovered_param", "tap_bypass", "tap_unthreaded"}, findings
