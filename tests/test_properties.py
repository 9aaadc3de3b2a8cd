"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.accountant import compute_epsilon, find_noise_multiplier
from repro.core.decision import (
    back_propagation,
    decide,
    ghost_is_cheaper,
    ghost_norm,
    grad_instantiation,
)
from repro.core.functions import abadi_clip, automatic_clip, global_clip
from repro.core.taps import TapMeta
from repro.data.poisson import poisson_sample_mask
from repro.nn.ssm_scan import chunked_ssm, ssm_reference
from repro.optim.compression import bf16_compress_with_error_feedback

SETTINGS = dict(max_examples=20, deadline=None)


@given(
    norms=st.lists(st.floats(1e-3, 1e4), min_size=1, max_size=16),
    clip_norm=st.floats(0.01, 10.0),
)
@settings(**SETTINGS)
def test_clip_functions_bounded(norms, clip_norm):
    """Any C(.; R) must satisfy C * ||g|| <= R (the DP sensitivity bound)."""
    n = jnp.asarray(norms, jnp.float32)
    for fn in (abadi_clip, global_clip, automatic_clip):
        c = fn(n, clip_norm)
        assert bool(jnp.all(c * n <= clip_norm * (1 + 1e-5))), fn.__name__
        assert bool(jnp.all(c >= 0))


@given(
    t=st.integers(1, 4096),
    d=st.integers(1, 4096),
    p=st.integers(1, 4096),
    k=st.sampled_from([1, 3, 5, 7]),
)
@settings(**SETTINGS)
def test_decision_rule_minimizes_space(t, d, p, k):
    """Eq (4.1) picks the branch with smaller clipping-module space cost."""
    big_d = d * k * k
    ghost_cost = ghost_norm(1, t, big_d, p).space
    inst_cost = grad_instantiation(1, t, big_d, p).space
    if ghost_is_cheaper(t, big_d, p, by="space"):
        assert ghost_cost <= inst_cost + 2  # +-1 element bookkeeping terms
    else:
        assert inst_cost <= ghost_cost + 2


@given(
    t=st.integers(1, 2048),
    d=st.integers(1, 2048),
    p=st.integers(1, 2048),
)
@settings(**SETTINGS)
def test_decision_rule_time_variant(t, d, p):
    gh = ghost_norm(1, t, d, p).time
    gi = grad_instantiation(1, t, d, p).time
    if ghost_is_cheaper(t, d, p, by="time"):
        assert gh <= gi + 2 * max(d, p) + 4
    else:
        assert gi <= gh + 2 * max(d, p) + 4


@given(
    sigma=st.floats(0.5, 20.0),
    steps=st.integers(1, 2000),
    q=st.floats(0.0005, 0.2),
)
@settings(max_examples=10, deadline=None)
def test_accountant_monotonicity(sigma, steps, q):
    delta = 1e-5
    e = compute_epsilon(q=q, sigma=sigma, steps=steps, delta=delta)
    assert e > 0
    assert compute_epsilon(q=q, sigma=sigma, steps=steps * 2, delta=delta) >= e
    assert compute_epsilon(q=q, sigma=sigma * 1.5, steps=steps, delta=delta) <= e
    assert compute_epsilon(q=q / 2, sigma=sigma, steps=steps, delta=delta) <= e + 1e-9


def test_sigma_search_roundtrip():
    s = find_noise_multiplier(target_epsilon=3.0, q=0.01, steps=1000, delta=1e-5)
    e = compute_epsilon(q=0.01, sigma=s, steps=1000, delta=1e-5)
    assert e <= 3.0
    assert e > 3.0 * 0.95  # not wastefully noisy


@given(data=st.data())
@settings(max_examples=10, deadline=None)
def test_chunked_ssm_matches_sequential(data):
    b = data.draw(st.integers(1, 2))
    t = data.draw(st.integers(1, 40))
    h = data.draw(st.integers(1, 3))
    dk = data.draw(st.sampled_from([2, 4, 8]))
    dv = data.draw(st.sampled_from([2, 4]))
    chunk = data.draw(st.sampled_from([4, 8, 16]))
    ks = jax.random.split(jax.random.PRNGKey(data.draw(st.integers(0, 100))), 4)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    y1, s1 = chunked_ssm(q, k, v, la, chunk=chunk)
    y2, s2 = ssm_reference(q, k, v, la)
    assert jnp.allclose(y1, y2, atol=1e-4)
    assert jnp.allclose(s1, s2, atol=1e-4)


def test_poisson_mask_statistics():
    key = jax.random.PRNGKey(0)
    masks = jax.vmap(lambda k: poisson_sample_mask(k, 1000, 0.1))(
        jax.random.split(key, 50)
    )
    rate = float(jnp.mean(masks))
    assert 0.09 < rate < 0.17  # q * slots_per_sample = 0.125 expected


def test_error_feedback_preserves_gradient_sum():
    """sum_t compressed_t == sum_t g_t + e_0 - e_T (telescoping)."""
    g = {"w": jnp.linspace(-1e-4, 1e-4, 128, dtype=jnp.float32)}
    ef = None
    total_comp = jnp.zeros_like(g["w"])
    total_g = jnp.zeros_like(g["w"])
    for i in range(20):
        gi = {"w": g["w"] * (1 + 0.1 * i)}
        comp, ef = bf16_compress_with_error_feedback(gi, ef)
        total_comp += comp["w"]
        total_g += gi["w"]
    resid = total_comp + ef["w"] - total_g
    assert float(jnp.max(jnp.abs(resid))) < 1e-6


@given(
    t=st.integers(1, 512),
    d=st.integers(1, 512),
    p=st.integers(1, 512),
)
@settings(**SETTINGS)
def test_decide_forced_branches(t, d, p):
    mk = lambda kind: TapMeta(kind=kind, T=t, D=d, p=p, s_shape=(1, t, p),
                              s_dtype=jnp.float32, param_path="x")
    assert decide(mk("embedding")) == "ghost"
    assert decide(mk("scale")) == "instantiate"
    assert decide(mk("bias")) == "instantiate"
    assert decide(mk("dw_conv")) == "instantiate"
    # one branch everywhere is a plan, not a mode
    for mode in ("ghost", "fastgradclip"):
        with pytest.raises(ValueError, match="unknown clipping mode"):
            decide(mk("matmul"), mode=mode)
