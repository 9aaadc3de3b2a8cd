"""Per-kernel validation: Pallas (interpret mode off-TPU, compiled on TPU)
and chunked-XLA ops vs the pure-jnp oracles, swept over shapes and dtypes;
plus the dispatch layer that routes between them."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.ghost_norm import ops as gops
from repro.kernels.ghost_norm.ghost_norm import (
    embedding_ghost_norm_sq_pallas,
    ghost_norm_sq_pallas,
    ghost_tiling,
)
from repro.kernels.ghost_norm.ref import (
    embedding_ghost_norm_sq_ref,
    ghost_norm_sq_ref,
    instantiated_norm_sq_ref,
)
from repro.kernels.psg_contract import ops as cops
from repro.kernels.psg_contract.psg_contract import (
    book_weighted_grad_pallas,
    psg_contract_pallas,
)
from repro.kernels.psg_contract.ref import (
    book_weighted_grad_ref,
    psg_contract_ref,
)
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_reference

on_tpu = jax.default_backend() == "tpu"
requires_tpu = pytest.mark.skipif(
    not on_tpu, reason="compiled (non-interpret) Pallas parity needs a TPU"
)


def _ghost_case(n, t, d, p, dt, block_t=32):
    return pytest.param(n, t, d, p, dt, block_t, id=f"{n}-{t}-{d}-{p}-{jnp.dtype(dt).name}")


GHOST_SHAPES = [
    # tiled: T >= block_t = 32, T and the features padded in HBM
    _ghost_case(3, 64, 16, 24, jnp.float32),
    _ghost_case(2, 100, 33, 7, jnp.float32),
    _ghost_case(1, 256, 128, 64, jnp.bfloat16),
    _ghost_case(4, 32, 8, 130, jnp.float32),
    # packed: T < block_t = 256; N a multiple of bn or not (a ragged last
    # step), widths lane multiples or not (27k, p = 10), chunks of 128 lanes
    _ghost_case(300, 1, 512, 10, jnp.float32, 256),
    _ghost_case(128, 4, 256, 128, jnp.float32, 256),
    _ghost_case(70, 4, 270, 10, jnp.bfloat16, 256),
    _ghost_case(32, 16, 384, 256, jnp.float32, 256),
    _ghost_case(20, 16, 54, 10, jnp.float32, 256),
    _ghost_case(9, 64, 128, 130, jnp.bfloat16, 256),
    _ghost_case(8, 64, 216, 64, jnp.float32, 256),
    _ghost_case(3, 197, 135, 10, jnp.bfloat16, 256),
    _ghost_case(2, 197, 128, 64, jnp.float32, 256),
]


@pytest.mark.parametrize("n,t,d,p,dt,block_t", GHOST_SHAPES)
def test_ghost_norm_pallas_vs_ref(n, t, d, p, dt, block_t):
    ks = jax.random.split(jax.random.PRNGKey(t * 7 + d), 2)
    a = jax.random.normal(ks[0], (n, t, d)).astype(dt)
    g = jax.random.normal(ks[1], (n, t, p)).astype(dt)
    got = ghost_norm_sq_pallas(a, g, block_t=block_t, block_f=32, interpret=True)
    want = ghost_norm_sq_ref(a, g)
    assert jnp.allclose(got, want, rtol=2e-4), float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("n,t,d,p,dt,block_t", GHOST_SHAPES)
def test_ghost_norm_chunked_vs_ref(n, t, d, p, dt, block_t):
    ks = jax.random.split(jax.random.PRNGKey(n * 31 + p), 2)
    a = jax.random.normal(ks[0], (n, t, d)).astype(dt)
    g = jax.random.normal(ks[1], (n, t, p)).astype(dt)
    got = gops.ghost_norm_sq(a, g, block=block_t)
    want = ghost_norm_sq_ref(a, g)
    assert jnp.allclose(got, want, rtol=2e-4)


# (N, T, D, p) of the taps the ghost norm takes in the benchmark's cells,
# written out from their tap discovery: VGG19 on CIFAR-10 at batch 1024
# (conv2_2, conv3_1..4, conv4_1..4, conv5_1..4, the head), xlstm-350m's LM
# head at 2 x 4096 tokens
VGG19_GHOST_TAPS = [
    (1024, 256, 1152, 128),
    (1024, 64, 1152, 256),
    *[(1024, 64, 2304, 256)] * 3,
    (1024, 16, 2304, 512),
    *[(1024, 16, 4608, 512)] * 3,
    *[(1024, 4, 4608, 512)] * 4,
    (1024, 1, 512, 10),
]
XLSTM_LM_HEAD = (2, 4096, 1024, 50304)


@pytest.mark.parametrize("tap", sorted(set(VGG19_GHOST_TAPS)) + [XLSTM_LM_HEAD])
def test_ghost_tiling_of_the_cells_taps(tap):
    n, t, d, p = tap
    path, bn, bfa, bfg = ghost_tiling(n, t, d, p)
    if t >= 256:  # conv2_2 and the LM head keep today's tiles
        assert (path, bn, bfa, bfg) == ("tiled", 1, 512, 512)
        return
    # packed: whole samples of unpadded T filling one 256-row tile, and
    # feature chunks that split the widths with no pad
    assert path == "packed"
    assert bn == 256 // t and bn * t == 256
    assert d % bfa == 0 and p % bfg == 0
    assert bfa == d or bfa % 128 == 0
    assert bfg == p or bfg % 128 == 0


def test_ghost_tiling_counts_vgg19_packed_calls():
    paths = [ghost_tiling(*tap)[0] for tap in VGG19_GHOST_TAPS]
    assert paths.count("packed") == 13 and paths[0] == "tiled"


def test_ghost_norm_chunked_path_forced():
    """Force the scan path (T > direct threshold is simulated via block)."""
    import repro.kernels.ghost_norm.ops as mod

    a = jax.random.normal(jax.random.PRNGKey(0), (2, 2048, 8))
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 2048, 4))
    got = mod.ghost_norm_sq(a, g, block=256)
    want = ghost_norm_sq_ref(a, g)
    assert jnp.allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("d_block", [8, 64])
def test_instantiated_norm_chunked(d_block):
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 20, 50))
    g = jax.random.normal(jax.random.PRNGKey(1), (3, 20, 6))
    got = gops.instantiated_norm_sq(a, g, block_d=d_block)
    want = instantiated_norm_sq_ref(a, g)
    assert jnp.allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("t,block", [(12, 1024), (300, 128)])
def test_embedding_ghost_norm(t, block):
    ids = jax.random.randint(jax.random.PRNGKey(0), (3, t), 0, 11)
    g = jax.random.normal(jax.random.PRNGKey(1), (3, t, 5))
    got = gops.embedding_ghost_norm_sq(ids, g, block=block)
    want = embedding_ghost_norm_sq_ref(ids, g)
    assert jnp.allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("t", [37, 41])
def test_embedding_ghost_norm_pallas_vs_ref(t):
    """Odd T forces the padded path — the two-sentinel machinery included."""
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, t), 0, 7)
    g = jax.random.normal(jax.random.PRNGKey(3), (3, t, 5))
    got = embedding_ghost_norm_sq_pallas(
        ids, g, block_t=16, block_f=8, interpret=not on_tpu
    )
    want = embedding_ghost_norm_sq_ref(ids, g)
    assert jnp.allclose(got, want, rtol=1e-4), float(jnp.max(jnp.abs(got - want)))


def test_embedding_pad_sentinels_never_match():
    """Regression for the single-sentinel padding bug: both id operands were
    padded with the same -1, so pad-vs-pad positions DID match and exactness
    silently rode on the cotangent being zero-padded.  With two distinct
    sentinels, no padded position of either operand may equal ANY position
    of the other — correctness no longer assumes anything about g's padding.
    This test fails if pad_ids_pair ever regresses to one shared sentinel.
    """
    t, block = 37, 16
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, t), 0, 50)
    ids_i, ids_j = gops.pad_ids_pair(ids, block)
    assert ids_i.shape == ids_j.shape == (2, 48)
    assert not bool(jnp.any(ids_i[:, t:, None] == ids_j[:, None, :]))
    assert not bool(jnp.any(ids_j[:, t:, None] == ids_i[:, None, :]))
    # real positions are untouched on both operands
    assert bool(jnp.all(ids_i[:, :t] == ids)) and bool(jnp.all(ids_j[:, :t] == ids))
    # no-padding case: the inputs come back unchanged
    even_i, even_j = gops.pad_ids_pair(ids_i[:, :32], block)
    assert even_i.shape == even_j.shape == (2, 32)
    # end to end: the padded scan path agrees with the oracle
    g = jax.random.normal(jax.random.PRNGKey(1), (2, t, 5))
    got = gops.embedding_ghost_norm_sq(ids, g, block=block)
    assert jnp.allclose(got, embedding_ghost_norm_sq_ref(ids, g), rtol=1e-4)


# ------------------------------------------------------- psg contraction --
BOOK_SHAPES = [
    (1, 64, 16, 24, jnp.float32),
    (2, 100, 33, 7, jnp.float32),
    (3, 37, 8, 130, jnp.float32),
    (1, 256, 64, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("m,r,d,p,dt", BOOK_SHAPES)
def test_book_weighted_grad_pallas_vs_ref(m, r, d, p, dt):
    ks = jax.random.split(jax.random.PRNGKey(r * 3 + d), 3)
    a = jax.random.normal(ks[0], (m, r, d)).astype(dt)
    g = jax.random.normal(ks[1], (m, r, p)).astype(dt)
    w = jax.random.uniform(ks[2], (m, r))
    got = book_weighted_grad_pallas(
        a, g, w, block_r=32, block_d=16, block_p=16, interpret=not on_tpu
    )
    want = book_weighted_grad_ref(a, g, w)
    tol = 5e-2 if dt == jnp.bfloat16 else 2e-4
    assert jnp.allclose(got, want, rtol=tol, atol=tol), float(
        jnp.max(jnp.abs(got - want))
    )


@pytest.mark.parametrize("m,r,d,p,dt", BOOK_SHAPES[:3])
def test_book_weighted_grad_xla_vs_ref(m, r, d, p, dt):
    ks = jax.random.split(jax.random.PRNGKey(m * 13 + p), 3)
    a = jax.random.normal(ks[0], (m, r, d)).astype(dt)
    g = jax.random.normal(ks[1], (m, r, p)).astype(dt)
    w = jax.random.uniform(ks[2], (m, r))
    assert jnp.allclose(
        cops.book_weighted_grad(a, g, w), book_weighted_grad_ref(a, g, w),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("n,f", [(5, 33), (64, 7), (3, 1024)])
def test_psg_contract_pallas_and_xla_vs_ref(n, f):
    ks = jax.random.split(jax.random.PRNGKey(n + f), 2)
    psg = jax.random.normal(ks[0], (n, f))
    c = jax.random.uniform(ks[1], (n,))
    want = psg_contract_ref(psg, c)
    got = psg_contract_pallas(psg, c, block_n=16, block_f=16, interpret=not on_tpu)
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)
    assert jnp.allclose(cops.psg_contract(psg, c), want, rtol=1e-5, atol=1e-5)


def test_dispatch_psg_contract_axis():
    """The bank layout carries the batch after the stack dims (axis=1)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    psg = jax.random.normal(ks[0], (3, 5, 4, 2))  # (lead, B, *param)
    c = jax.random.uniform(ks[1], (5,))
    want = jnp.einsum("lb...,b->l...", psg, c)
    for impl in ("xla", "pallas"):
        got = dispatch.psg_contract(psg, c, axis=1, impl=impl)
        assert got.shape == (3, 4, 2)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5), impl


# ------------------------------------------------------------- dispatch --
def test_dispatch_constants_mirror_plan_validation():
    """plan.py duplicates the op/impl vocab to stay import-free of the
    kernels package; the two must never drift."""
    from repro.tuner.plan import KERNEL_IMPLS, KERNEL_OPS

    assert KERNEL_OPS == dispatch.OPS
    assert KERNEL_IMPLS == dispatch.IMPLS


def test_dispatch_defaults_follow_backend():
    expected = "pallas" if on_tpu else "xla"
    for op in dispatch.OPS:
        assert dispatch.default_impl(op) == expected
        assert dispatch.resolve(op) == expected
        # an explicit argument always wins
        assert dispatch.resolve(op, "xla") == "xla"
    if on_tpu:
        assert dispatch.available_impls() == ("pallas", "xla")
    else:
        assert dispatch.available_impls() == ("xla",)


def test_dispatch_force_impl_and_validation():
    with dispatch.force_impl("pallas"):
        assert dispatch.resolve("ghost_norm") == "pallas"
        assert dispatch.resolve("psg_contract") == "pallas"
        # nested per-op override wins over the blanket one
        with dispatch.force_impl(psg_contract="xla"):
            assert dispatch.resolve("psg_contract") == "xla"
            assert dispatch.resolve("ghost_norm") == "pallas"
        assert dispatch.resolve("psg_contract") == "pallas"
    # context restored
    assert dispatch.resolve("ghost_norm") == dispatch.default_impl("ghost_norm")
    with pytest.raises(ValueError):
        dispatch.resolve("ghost_norm", "cuda")
    with pytest.raises(ValueError):
        dispatch.resolve("not_an_op", "xla")
    with pytest.raises(ValueError):
        dispatch.default_impl("not_an_op")
    with pytest.raises(ValueError):
        with dispatch.force_impl("banana"):
            pass
    with pytest.raises(ValueError):
        with dispatch.force_impl(not_an_op="xla"):
            pass


def test_dispatch_ops_agree_across_impls():
    """Both impls of every dispatch op compute the same values."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    a = jax.random.normal(ks[0], (2, 40, 12))
    g = jax.random.normal(ks[1], (2, 40, 6))
    c = jax.random.uniform(ks[2], (2,))
    ids = jax.random.randint(ks[3], (2, 40), 0, 9)
    pairs = [
        lambda impl: dispatch.ghost_norm_sq(a, g, block=16, impl=impl),
        lambda impl: dispatch.embedding_ghost_norm_sq(ids, g, block=16, impl=impl),
        lambda impl: dispatch.book_weighted_grad(
            a, g, jnp.broadcast_to(c[:, None], (2, 40)), impl=impl
        ),
        lambda impl: dispatch.psg_contract(a, c, impl=impl),
    ]
    for fn in pairs:
        x, y = fn("xla"), fn("pallas")
        assert jnp.allclose(x, y, rtol=2e-4, atol=2e-4), float(
            jnp.max(jnp.abs(x - y))
        )


@pytest.mark.parametrize("window,n_kv", [(None, 8), (9, 8), (None, 2)])
def test_dispatch_flash_attention_impls_agree(window, n_kv):
    """Serving attention through dispatch: pallas == xla on the static-mask
    cases, including sliding windows and GQA head grouping."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s, h, hd = 2, 37, 8, 16
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, n_kv, hd))
    v = jax.random.normal(ks[2], (b, s, n_kv, hd))
    x = dispatch.flash_attention(q, k, v, causal=True, window=window,
                                 impl="xla")
    p = dispatch.flash_attention(q, k, v, causal=True, window=window,
                                 impl="pallas")
    assert jnp.allclose(x, p, rtol=2e-5, atol=2e-5), float(
        jnp.max(jnp.abs(x - p))
    )


def test_dispatch_flash_attention_dynamic_args_fall_back():
    """Ring positions / fill levels / traced offsets have no pallas path;
    a forced pallas choice must still produce the XLA result."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    b, s, h, hd = 1, 16, 4, 8
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    pos = jnp.where(jnp.arange(s) < 10, jnp.arange(s), -1)
    want = dispatch.flash_attention(
        q, k, v, causal=True, q_offset=jnp.asarray(9), kv_positions=pos,
        impl="xla")
    with dispatch.force_impl(flash_attention="pallas"):
        got = dispatch.flash_attention(
            q, k, v, causal=True, q_offset=jnp.asarray(9), kv_positions=pos)
    assert jnp.array_equal(want, got)


# ------------------------------------- compiled TPU parity (non-interpret) --
@requires_tpu
@pytest.mark.parametrize("n,t,d,p,mxu_dtype", [
    (4, 300, 96, 48, None),  # tiled
    # VGG19's packed taps at N = 64, f32 as the config stores them.  The
    # kernel's dots take the MXU's default one-pass bf16 operands, which
    # move the head's |a|^2 |g|^2 by ~4e-3: the reference multiplies the
    # same rounded operands, exactly
    (64, 16, 4608, 512, jnp.bfloat16),  # conv4_2
    (64, 4, 4608, 512, jnp.bfloat16),  # conv5_1
    (64, 1, 512, 10, jnp.bfloat16),  # the head
])
def test_tpu_ghost_norm_compiled_parity(n, t, d, p, mxu_dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    a = jax.random.normal(ks[0], (n, t, d))
    g = jax.random.normal(ks[1], (n, t, p))
    got = ghost_norm_sq_pallas(a, g, interpret=False)
    if mxu_dtype is None:
        want = ghost_norm_sq_ref(a, g)
    else:
        with jax.default_matmul_precision("highest"):
            want = ghost_norm_sq_ref(a.astype(mxu_dtype), g.astype(mxu_dtype))
    assert jnp.allclose(got, want, rtol=2e-4)


@requires_tpu
def test_tpu_embedding_ghost_norm_compiled_parity():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    ids = jax.random.randint(ks[0], (4, 300), 0, 1000)
    g = jax.random.normal(ks[1], (4, 300, 64))
    got = embedding_ghost_norm_sq_pallas(ids.astype(jnp.float32), g, interpret=False)
    assert jnp.allclose(got, embedding_ghost_norm_sq_ref(ids, g), rtol=2e-4)


@requires_tpu
def test_tpu_psg_contract_compiled_parity():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    a = jax.random.normal(ks[0], (2, 700, 130))
    g = jax.random.normal(ks[1], (2, 700, 70))
    w = jax.random.uniform(ks[2], (2, 700))
    got = book_weighted_grad_pallas(a, g, w, interpret=False)
    assert jnp.allclose(got, book_weighted_grad_ref(a, g, w), rtol=2e-4, atol=2e-4)
    psg = jax.random.normal(ks[0], (48, 1300))
    c = jax.random.uniform(ks[1], (48,))
    got = psg_contract_pallas(psg, c, interpret=False)
    assert jnp.allclose(got, psg_contract_ref(psg, c), rtol=2e-4, atol=2e-4)


ATTN_CASES = [
    (2, 64, 64, 4, 2, 16, True, None, 0),
    (1, 128, 128, 4, 4, 8, True, 32, 0),
    (2, 1, 96, 4, 2, 16, True, None, 57),
    (2, 48, 48, 6, 2, 32, False, None, 0),
    (1, 100, 100, 2, 1, 16, True, None, 0),
]


@pytest.mark.parametrize("b,sq,skv,h,kh,hd,causal,window,qoff", ATTN_CASES)
def test_flash_xla_forward(b, sq, skv, h, kh, hd, causal, window, qoff):
    ks = jax.random.split(jax.random.PRNGKey(sq + skv), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd))
    k = jax.random.normal(ks[1], (b, skv, kh, hd))
    v = jax.random.normal(ks[2], (b, skv, kh, hd))
    got = flash_attention(q, k, v, causal=causal, window=window, q_offset=qoff,
                          block_q=32, block_kv=32)
    want = mha_reference(q, k, v, causal=causal, window=window, q_offset=qoff)
    assert jnp.allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("b,sq,skv,h,kh,hd,causal,window,qoff", ATTN_CASES[:2])
def test_flash_xla_gradients(b, sq, skv, h, kh, hd, causal, window, qoff):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd))
    k = jax.random.normal(ks[1], (b, skv, kh, hd))
    v = jax.random.normal(ks[2], (b, skv, kh, hd))
    f = lambda *a: flash_attention(*a, causal=causal, window=window,
                                   q_offset=qoff, block_q=32, block_kv=32).sum()
    r = lambda *a: mha_reference(*a, causal=causal, window=window,
                                 q_offset=qoff).astype(jnp.float32).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for x, y in zip(gf, gr):
        assert jnp.allclose(x, y, atol=3e-5)


@pytest.mark.parametrize(
    "b,h,sq,skv,hd,causal,window,qoff,dt",
    [
        (2, 3, 64, 64, 16, True, None, 0, jnp.float32),
        (1, 2, 100, 100, 32, True, 24, 0, jnp.float32),
        (1, 2, 1, 96, 16, True, None, 95, jnp.float32),
        (2, 2, 48, 48, 16, False, None, 0, jnp.bfloat16),
    ],
)
def test_flash_pallas_vs_ref(b, h, sq, skv, hd, causal, window, qoff, dt):
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd)).astype(dt)
    k = jax.random.normal(ks[1], (b, skv, h, hd)).astype(dt)
    v = jax.random.normal(ks[2], (b, skv, h, hd)).astype(dt)
    got = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window, q_offset=qoff,
        block_q=16, block_kv=32, interpret=True,
    ).transpose(0, 2, 1, 3)
    want = mha_reference(q, k, v, causal=causal, window=window, q_offset=qoff)
    tol = 5e-3 if dt == jnp.bfloat16 else 2e-5
    assert jnp.allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=tol)
