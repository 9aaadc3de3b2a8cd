"""The paper's central correctness claim (Sec. 2.1): mixed ghost clipping is
*exactly* the same mechanism as per-sample-gradient clipping — only cheaper.

Every mode must produce the same per-sample norms and the same clipped
gradient sum as the vmap(grad) oracle, across every layer family the
framework supports.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.clipping import (
    ClipConfig,
    discover_meta,
    dp_value_and_clipped_grad,
    validate_coverage,
)
from repro.core.taps import Ctx
from repro.nn.attention import Attention
from repro.nn.conv import Conv2d, global_avg_pool
from repro.nn.mamba import MambaBlock
from repro.nn.mlp import GatedMLP
from repro.nn.module import Dense, Embedding, GroupNorm, LayerNorm, Module, RMSNorm
from repro.nn.moe import MoE
from repro.nn.stack import ScannedStack
from repro.nn.xlstm import MLSTMBlock, SLSTMBlock

from helpers import forced_plan, lm_batch, max_tree_diff

MODES = ["mixed_ghost", "bk_mixed"]
# (name, mode, branch forced on every matmul tap): the paper's ghost-only and
# instantiate-only rivals, and book-keeping replaying the (a, g) book
FORCED = [
    ("ghost", "mixed_ghost", "ghost"),
    ("instantiate", "mixed_ghost", "instantiate"),
    ("bk_book", "bk_mixed", "ghost"),
]


def _run_all_modes(loss_with_ctx, params, batch, clip_norm=0.3):
    cfgs = {mode: ClipConfig(mode=mode, clip_norm=clip_norm) for mode in ["vmap"] + MODES}
    for name, mode, branch in FORCED:
        plan = forced_plan(loss_with_ctx, params, batch, branch)
        cfgs[name] = ClipConfig(mode=mode, clip_norm=clip_norm, plan=plan)
    return {
        name: jax.jit(dp_value_and_clipped_grad(loss_with_ctx, cfg))(params, batch)
        for name, cfg in cfgs.items()
    }


def _assert_matches(results, tol=5e-5):
    ref_loss, ref_g, ref_aux = results["vmap"]
    scale = max(float(jnp.max(ref_aux["per_sample_norms"])), 1.0)
    for mode in MODES + [name for name, _, _ in FORCED]:
        loss, g, aux = results[mode]
        assert jnp.allclose(loss, ref_loss, rtol=1e-5), mode
        nerr = float(jnp.max(jnp.abs(aux["per_sample_norms"] - ref_aux["per_sample_norms"])))
        assert nerr / scale < tol, (mode, nerr, scale)
        gerr = max_tree_diff(ref_g, g)
        assert gerr < tol, (mode, gerr)


class _MLPModel:
    def __init__(self, vocab=17, d=8, f=12, key=jax.random.PRNGKey(0)):
        self.emb = Embedding("emb", vocab, d)
        self.l1 = Dense("l1", d, f, use_bias=True)
        self.norm = RMSNorm("n", f)
        self.l2 = Dense("l2", f, vocab, use_bias=False)
        ks = jax.random.split(key, 4)
        self.params = {
            "emb": self.emb.init(ks[0]), "l1": self.l1.init(ks[1]),
            "n": self.norm.init(ks[2]), "l2": self.l2.init(ks[3]),
        }

    def loss_with_ctx(self, params, batch, ctx):
        x = self.emb(params["emb"], batch["tokens"], ctx.scope("emb"))
        h = jax.nn.gelu(self.l1(params["l1"], x, ctx.scope("l1")))
        h = self.norm(params["n"], h, ctx.scope("n"))
        logits = self.l2(params["l2"], h, ctx.scope("l2"))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        nll = nll * batch["mask"][:, None]
        return jnp.mean(nll, axis=-1)


@pytest.mark.parametrize("mode", ["ghost", "fastgradclip", "ghost_taps", "fastgradclip_taps",
                                  "mixed_ghost_taps", "bk_mixed_taps"])
def test_fixed_branch_and_taps_modes_are_gone(mode):
    """Four modes; one branch everywhere is a plan (``forced_plan``), and a
    config naming a removed mode fails where it is built."""
    from repro.core.clipping import MODES

    assert MODES == ("non_private", "vmap", "mixed_ghost", "bk_mixed")
    with pytest.raises(ValueError, match="unknown clipping mode"):
        ClipConfig(mode=mode)


def test_dense_embedding_norm_exactness():
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 4, 6, 17)
    _assert_matches(_run_all_modes(m.loss_with_ctx, m.params, batch))


def test_poisson_mask_zeroes_contributions():
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 4, 6, 17)
    batch["mask"] = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    res = _run_all_modes(m.loss_with_ctx, m.params, batch)
    _assert_matches(res)
    # masked samples must have zero clip factor
    _, _, aux = res["mixed_ghost"]
    assert float(aux["clip_factors"][1]) == 0.0
    assert float(aux["clip_factors"][3]) == 0.0


def test_coverage_validation_catches_untapped_params():
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 2, 4, 17)

    def leaky_loss(params, b, ctx):
        # l1 applied WITHOUT dp taps (dp disabled via Ctx.disabled scope hack)
        x = m.emb(params["emb"], b["tokens"], ctx.scope("emb"))
        h = jax.nn.gelu(m.l1(params["l1"], x, Ctx.disabled()))
        h = m.norm(params["n"], h, ctx.scope("n"))
        logits = m.l2(params["l2"], h, ctx.scope("l2"))
        return jnp.mean(logits, axis=(1, 2))

    meta = discover_meta(leaky_loss, m.params, batch)
    missing = validate_coverage(meta, m.params)
    assert "l1/w" in missing and "l1/b" in missing


def test_conv2d_exactness():
    gn = GroupNorm("gn", 8, groups=4)
    c1 = Conv2d("c1", 3, 8, (3, 3), padding="SAME")
    c2 = Conv2d("c2", 8, 8, (3, 3), strides=(2, 2), padding="SAME")
    head = Dense("head", 8, 10)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {"c1": c1.init(ks[0]), "gn": gn.init(ks[1]), "c2": c2.init(ks[2]),
              "head": head.init(ks[3])}

    def loss(params, batch, ctx):
        h = c1(params["c1"], batch["image"], ctx.scope("c1"))
        h = jax.nn.relu(gn(params["gn"], h, ctx.scope("gn")))
        h = c2(params["c2"], h, ctx.scope("c2"))
        h = global_avg_pool(h)
        logits = head(params["head"], h[:, None, :], ctx.scope("head"))[:, 0]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]

    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(4), (4, 10, 10, 3)),
        "y": jax.random.randint(jax.random.PRNGKey(5), (4,), 0, 10),
    }
    _assert_matches(_run_all_modes(loss, params, batch))


@pytest.mark.parametrize("kernel,strides,padding,dilation", [
    ((3, 3), (1, 1), "SAME", None),
    ((3, 3), (2, 2), "SAME", None),
    ((3, 2), (1, 2), "VALID", None),
    ((4, 4), (4, 4), "VALID", None),
    ((3, 3), (1, 1), ((2, 0), (1, 3)), None),
    ((3, 3), (1, 1), "SAME", (2, 2)),
    ((2, 3), (2, 1), "SAME", (1, 2)),
])
def test_unfold2d_is_offset_major(kernel, strides, padding, dilation):
    """unfold2d holds the conv's patches with features (kh_i*kw + kw_i)*d + c,
    the weight's own (kh, kw, d, p) order: patches x weight give the conv."""
    from repro.core.taps import ConvInfo
    from repro.nn.conv import unfold2d

    info = ConvInfo(kernel, strides, padding, rhs_dilation=dilation)
    kx, kw = jax.random.split(jax.random.PRNGKey(6))
    x = jax.random.normal(kx, (2, 9, 11, 5))
    w = jax.random.normal(kw, kernel + (5, 4))
    dn = ("NHWC", "HWIO", "NHWC")
    got = unfold2d(x, info)
    # conv_general_dilated_patches orders the features channel-major
    want = jax.lax.conv_general_dilated_patches(
        x, kernel, strides, padding, rhs_dilation=dilation, dimension_numbers=dn
    )
    n, ho, wo, _ = want.shape
    want = want.reshape(n, ho * wo, 5, -1).transpose(0, 1, 3, 2).reshape(got.shape)
    assert jnp.array_equal(got, want)
    conv = jax.lax.conv_general_dilated(
        x, w, strides, padding, rhs_dilation=dilation, dimension_numbers=dn,
        precision=jax.lax.Precision.HIGHEST,
    )
    via = jnp.einsum("ntk,kp->ntp", got, w.reshape(-1, 4),
                     precision=jax.lax.Precision.HIGHEST)
    assert jnp.allclose(via, conv.reshape(via.shape), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,kernel,padding", [
    ((2, 32, 32, 3), (8, 8), "VALID"),  # a ViT patch embedding
    ((2, 9, 11, 5), (4, 4), "VALID"),  # rows and columns left over
    ((2, 8, 12, 5), (4, 2), "SAME"),  # SAME that pads nothing
    ((1, 6, 6, 2), (3, 3), ((0, 0), (0, 0))),
])
def test_unfold2d_patchify_equals_slicing(shape, kernel, padding):
    """Where stride = kernel with no padding or dilation, unfold2d reshapes
    and transposes: the same patches, in the same order, as the slices."""
    from repro.core.taps import ConvInfo
    from repro.nn.conv import _unfold2d_slices, unfold2d

    info = ConvInfo(kernel, kernel, padding)
    x = jax.random.normal(jax.random.PRNGKey(7), shape)
    pads = padding if not isinstance(padding, str) else jax.lax.padtype_to_pads(
        shape[1:3], kernel, kernel, padding)
    assert jnp.array_equal(unfold2d(x, info), _unfold2d_slices(x, info, pads))


class _StackModel(Module):
    def __init__(self):
        d = 16
        self.d = d

        class Block(Module):
            def __init__(self):
                self.n1 = RMSNorm("n1", d)
                self.attn = Attention("attn", d, 4, 2, block_q=4, block_kv=4)
                self.n2 = RMSNorm("n2", d)
                self.moe = MoE("moe", d, 20, n_experts=4, top_k=2)

            def init(self, key):
                ks = jax.random.split(key, 4)
                return {"n1": self.n1.init(ks[0]), "attn": self.attn.init(ks[1]),
                        "n2": self.n2.init(ks[2]), "moe": self.moe.init(ks[3])}

            def __call__(self, params, x, ctx, cache=None, **kw):
                h, _ = self.attn(params["attn"], self.n1(params["n1"], x, ctx.scope("n1")),
                                 ctx.scope("attn"))
                x = x + h
                x = x + self.moe(params["moe"], self.n2(params["n2"], x, ctx.scope("n2")),
                                 ctx.scope("moe"))
                return x, cache

        self.emb = Embedding("emb", 13, d)
        self.stack = ScannedStack("layers", Block(), 2, remat=True)
        self.head = Dense("head", d, 13, use_bias=False)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        self.params = {"emb": self.emb.init(ks[0]), "layers": self.stack.init(ks[1]),
                       "head": self.head.init(ks[2])}

    def loss_with_ctx(self, params, batch, ctx):
        x = self.emb(params["emb"], batch["tokens"], ctx.scope("emb"))
        x, _ = self.stack(params["layers"], x, ctx.scope("layers"))
        logits = self.head(params["head"], x, ctx.scope("head"))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        return jnp.mean(nll, axis=-1)


def test_scanned_stack_attention_moe_exactness():
    m = _StackModel()
    batch = lm_batch(jax.random.PRNGKey(1), 3, 6, 13)
    _assert_matches(_run_all_modes(m.loss_with_ctx, m.params, batch))


def test_ssm_blocks_exactness():
    d, v = 8, 11
    mamba = MambaBlock("m", d, expand=2, head_dim=4, d_state=4, chunk=4)
    mls = MLSTMBlock("ml", d, n_heads=2, chunk=4)
    sls = SLSTMBlock("sl", d, n_heads=2)
    emb = Embedding("emb", v, d)
    head = Dense("head", d, v, use_bias=False)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {"emb": emb.init(ks[0]), "mamba": mamba.init(ks[1]),
              "mlstm": mls.init(ks[2]), "slstm": sls.init(ks[3]),
              "head": head.init(ks[4])}

    def loss(params, batch, ctx):
        x = emb(params["emb"], batch["tokens"], ctx.scope("emb"))
        h, _ = mamba(params["mamba"], x, ctx.scope("mamba"))
        x = x + h
        x, _ = mls(params["mlstm"], x, ctx.scope("mlstm"))
        x, _ = sls(params["slstm"], x, ctx.scope("slstm"))
        logits = head(params["head"], x, ctx.scope("head"))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        return jnp.mean(nll, axis=-1)

    batch = lm_batch(jax.random.PRNGKey(1), 3, 7, v)
    meta = discover_meta(loss, params, batch)
    assert not validate_coverage(meta, params)
    _assert_matches(_run_all_modes(loss, params, batch))


def test_decision_modes_agree_on_gradients_not_costs():
    """ghost vs instantiate pick different branches but identical results."""
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(2), 4, 6, 17)
    meta = discover_meta(m.loss_with_ctx, m.params, batch)
    from repro.core.decision import decide

    branches_space = {k: decide(v, mode="mixed_ghost", by="space") for k, v in meta.items()}
    branches_time = {k: decide(v, mode="mixed_ghost", by="time") for k, v in meta.items()}
    branches_bk = {k: decide(v, mode="bk_mixed") for k, v in meta.items()}
    assert set(branches_space.values()) <= {"ghost", "instantiate"}
    assert set(branches_time.values()) <= {"ghost", "instantiate"}
    assert set(branches_bk.values()) <= {"ghost", "instantiate"}


def test_coverage_validation_raises_on_duplicate_taps():
    """Two taps claiming the same param leaf double-count its norm: raise."""
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 2, 4, 17)

    def doubled_loss(params, b, ctx):
        # the same Dense applied twice under different tap names but the
        # SAME param path: classic accidental weight sharing
        x = m.emb(params["emb"], b["tokens"], ctx.scope("emb"))
        h = jax.nn.gelu(m.l1(params["l1"], x, ctx.scope("l1")))
        h = h + m.l1(params["l1"], x, ctx.scope("l1_again").scope("l1"))
        h = m.norm(params["n"], h, ctx.scope("n"))
        logits = m.l2(params["l2"], h, ctx.scope("l2"))
        return jnp.mean(logits, axis=(1, 2))

    meta = discover_meta(doubled_loss, m.params, batch)
    # rewrite the duplicate tap's param_path back to the shared leaf (the
    # scope prefix would otherwise make it a distinct — missing — path)
    import dataclasses as _dc

    dup = {}
    for name, mm in meta.items():
        if name.startswith("l1_again/"):
            mm = _dc.replace(mm, param_path="l1/w", bias_path="l1/b")
        dup[name] = mm
    with pytest.raises(ValueError) as e:
        validate_coverage(dup, m.params)
    assert "l1/out" in str(e.value) and "l1_again/l1/out" in str(e.value)
    assert "double-counted" in str(e.value)


def test_frozen_prefixes_bk_and_ghost_agree_on_covered_leaves():
    """Untapped-but-frozen params: clean coverage, zero bk grads, and the
    fused bk gradients still match mixed_ghost on every covered leaf."""
    m = _MLPModel()
    frozen_head = Dense("l2", 12, 17, use_bias=False, dp=False)

    def loss(params, b, ctx):
        x = m.emb(params["emb"], b["tokens"], ctx.scope("emb"))
        h = jax.nn.gelu(m.l1(params["l1"], x, ctx.scope("l1")))
        logits = frozen_head(params["l2"], h, ctx.scope("l2"))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, b["labels"][..., None], axis=-1)[..., 0]
        return jnp.mean(nll * b["mask"][:, None], axis=-1)

    params = {"emb": m.params["emb"], "l1": m.params["l1"],
              "l2": frozen_head.init(jax.random.PRNGKey(7))}
    batch = lm_batch(jax.random.PRNGKey(1), 4, 6, 17)
    batch["mask"] = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    meta = discover_meta(loss, params, batch)
    assert validate_coverage(meta, params) == ["l2/w"]
    assert validate_coverage(meta, params, frozen_prefixes=("l2",)) == []

    cfg = dict(clip_norm=0.3, frozen_prefixes=("l2",))
    book = forced_plan(loss, params, batch, "ghost")  # every tap banks (a, g)
    out = {}
    for name, mode, plan in [("mixed_ghost", "mixed_ghost", None), ("bk_mixed", "bk_mixed", None),
                             ("bk_book", "bk_mixed", book)]:
        fn = jax.jit(dp_value_and_clipped_grad(loss, ClipConfig(mode=mode, plan=plan, **cfg)))
        out[name] = fn(params, batch)
    _, g_ref, aux_ref = out["mixed_ghost"]
    for mode in ["bk_mixed", "bk_book"]:
        _, g, aux = out[mode]
        assert jnp.allclose(
            aux["per_sample_norms"], aux_ref["per_sample_norms"], atol=1e-5
        ), mode
        # frozen leaf: book-keeping owes it nothing (zeros) — the
        # second-backward engine reports its unclipped weighted grad, which
        # is why frozen params must never reach the optimizer
        assert float(jnp.max(jnp.abs(g["l2"]["w"]))) == 0.0
        for key in ("emb", "l1"):
            assert max_tree_diff(g_ref[key], g[key]) < 5e-5, (mode, key)


def test_kernel_choice_flips_cost_not_math():
    """The psg-contraction (and every other dispatch op) in the oracle
    matrix with the kernel choice flipped both ways: Pallas and XLA impls
    must produce the same losses, per-sample norms, and clipped gradients —
    a kernel choice moves timings only."""
    from repro.kernels import dispatch

    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 4, 6, 17)
    book = forced_plan(m.loss_with_ctx, m.params, batch, "ghost")

    def run(mode, plan, impl):
        # build + trace inside the context: dispatch resolves at trace time
        with dispatch.force_impl(impl):
            fn = dp_value_and_clipped_grad(
                m.loss_with_ctx, ClipConfig(mode=mode, clip_norm=0.3, plan=plan)
            )
            return fn(m.params, batch)

    # bk_mixed under ``book``: every matmul tap contracts its (a, g) book
    for mode, plan in [("mixed_ghost", None), ("bk_mixed", None), ("bk_mixed", book)]:
        l_x, g_x, aux_x = run(mode, plan, "xla")
        l_p, g_p, aux_p = run(mode, plan, "pallas")
        assert jnp.allclose(l_x, l_p, rtol=1e-6), mode
        assert jnp.allclose(
            aux_x["per_sample_norms"], aux_p["per_sample_norms"], atol=2e-5
        ), mode
        assert max_tree_diff(g_x, g_p) < 2e-5, mode


def test_embedding_vocab_guard_raises_on_fused_engines():
    """Ids cross the fused bank side channel as fp32: a vocab >= 2^24 would
    silently corrupt high token ids, so tracing must raise — on the norm
    path and the book-keeping weighted-grad path alike.  Integer ids (an
    explicit tap) stay exact and usable."""
    import dataclasses as _dc

    import repro.core.ghost as ghost_mod
    from repro.core.taps import TapMeta

    big_vocab = ghost_mod.MAX_EXACT_FP32_ID  # == 2^24: first size the (
    # deliberately conservative) guard rejects
    b, t, p = 2, 4, 3
    meta = TapMeta(
        kind="embedding", T=t, D=big_vocab, p=p, s_shape=(b, t, p),
        s_dtype=jnp.float32, param_path="emb/e", batch_size=b, fused=True,
        a_shape=(b, t), a_dtype=jnp.float32,
    )
    ids_f32 = jnp.zeros((b, t), jnp.float32)
    ids_int = jnp.zeros((b, t), jnp.int32)
    g = jnp.ones((b, t, p), jnp.float32)

    # norm path, fp32 ids (fused engine): trace-time error
    with pytest.raises(ValueError, match="2\\^24"):
        ghost_mod.tap_norm_sq(meta, ids_f32, g)
    # bank path (bk_mixed): same guard before anything is banked
    with pytest.raises(ValueError, match="2\\^24"):
        ghost_mod.tap_bank(meta, ids_f32, g, mode="bk_mixed")
    # weighted-grad path from a banked book: guarded before the round-trip
    with pytest.raises(ValueError, match="banked-id round-trip"):
        ghost_mod.bank_weighted_grads(
            meta, {"a": ids_f32, "g": g, "n": jnp.ones((b,))},
            jnp.ones((b,)), (big_vocab, p),
        )
    # integer ids (an explicit tap) are exact at any vocab: no raise
    out = ghost_mod.tap_norm_sq(meta, ids_int, g)
    assert out.shape == (b,)
    # one id below the limit: fp32 is exact and the fused engine works
    ok_meta = _dc.replace(meta, D=big_vocab - 1)
    out = ghost_mod.tap_norm_sq(ok_meta, ids_f32, g)
    assert out.shape == (b,)


def test_fused_bk_never_pays_the_explicit_engine_memory():
    """The fused bk engine holds no tap-sized zeros and no activation dict:
    on XLA's compiled peak-memory model it stays below the second-backward
    engine and within 1.5x of the non-private step (the zero-taps +
    acts-dict formulation of book-keeping took 2.6x on this model)."""
    gn = GroupNorm("gn", 8, groups=4)
    c1 = Conv2d("c1", 3, 8, (3, 3), padding="SAME")
    head = Dense("head", 8, 10)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    params = {"c1": c1.init(ks[0]), "gn": gn.init(ks[1]), "head": head.init(ks[2])}

    def loss(params, batch, ctx):
        h = jax.nn.relu(gn(params["gn"],
                           c1(params["c1"], batch["image"], ctx.scope("c1")),
                           ctx.scope("gn")))
        h = global_avg_pool(h)
        logits = head(params["head"], h[:, None, :], ctx.scope("head"))[:, 0]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]

    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(4), (16, 16, 16, 3)),
        "y": jax.random.randint(jax.random.PRNGKey(5), (16,), 0, 10),
    }
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, batch)
    )

    def peak(mode):
        fn = dp_value_and_clipped_grad(loss, ClipConfig(mode=mode))
        ma = jax.jit(fn).lower(*specs).compile().memory_analysis()
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes - ma.alias_size_in_bytes)

    assert peak("bk_mixed") < peak("mixed_ghost")
    assert peak("bk_mixed") < 1.5 * peak("non_private")
