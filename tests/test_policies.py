"""repro.policies: the policy x executor-mode exactness oracle, quantile
accounting, plan/consensus policy gates, and checkpoint round-trips.

The central claims:
- every policy (fixed / automatic / quantile / per_layer) produces clipped
  gradients matching a naive per-sample-gradient reference on EVERY executor
  family (vmap / fused second-backward / book-keeping);
- an adversarially flipped-branch tuner plan changes no policy's output
  (branch decisions are policy-independent);
- the quantile policy's indicator release is billed exactly (manual RDP
  composition), including through the target-epsilon bisection;
- policy state survives checkpoint save/restore bit-identically and resumes
  to the same trajectory;
- a fleet cannot agree across ranks running different policies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clipping import ClipConfig, discover_meta, dp_value_and_clipped_grad
from repro.core.decision import decide
from repro.core.taps import Ctx
from repro.policies import (
    AutomaticPolicy,
    FixedPolicy,
    PerLayerPolicy,
    QuantilePolicy,
    make_policy,
)
from repro.nn.module import Dense, Embedding, RMSNorm
from repro.tuner.plan import ClipPlan, device_string, shape_fingerprint
from repro.utils.tree import flatten_dict

from helpers import lm_batch, max_tree_diff

MODES = ["vmap", "mixed_ghost", "bk_mixed"]


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

    def init(self, key):  # make_train_state contract; deterministic params
        del key
        return self.params

    def loss_with_ctx(self, params, batch, ctx):
        x = self.emb(params["emb"], batch["tokens"], ctx.scope("emb"))
        h = jax.nn.gelu(self.l1(params["l1"], x, ctx.scope("l1")))
        h = self.norm(params["n"], h, ctx.scope("n"))
        logits = self.l2(params["l2"], h, ctx.scope("l2"))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        nll = nll * batch["mask"][:, None]
        return jnp.mean(nll, axis=-1)


def _setup(mask=(1.0, 1.0, 0.0, 1.0)):
    m = _MLPModel()
    batch = lm_batch(jax.random.PRNGKey(1), 4, 6, 17)
    batch["mask"] = jnp.asarray(mask)
    return m, m.params, batch


def _per_sample_grads(m, params, batch):
    def single(p, ex):
        return m.loss_with_ctx(p, ex, Ctx.disabled())[0]

    per_ex = jax.tree_util.tree_map(lambda x: x[:, None], batch)
    return jax.vmap(lambda ex: jax.grad(single)(params, ex))(per_ex)


def _naive_reference(policy, pstate, m, params, batch):
    """Clipped grad sum from raw per-sample grads + hand-written policy math."""
    psg = _per_sample_grads(m, params, batch)
    flat = {k: np.asarray(v, np.float64) for k, v in flatten_dict(psg).items()}
    b = batch["mask"].shape[0]
    leaf_norms2 = {
        k: (v.reshape(b, -1) ** 2).sum(axis=1) for k, v in flat.items()
    }
    norms = np.sqrt(sum(leaf_norms2.values()))
    mask = np.asarray(batch["mask"], np.float64)

    def abadi(n, r):
        return np.minimum(r / np.maximum(n, 1e-12), 1.0)

    if isinstance(policy, PerLayerPolicy):
        th = np.asarray(pstate["thresholds"], np.float64)
        g_norms2 = {}
        for path, n2 in leaf_norms2.items():
            gi = policy.group_of(path)
            g_norms2[gi] = g_norms2.get(gi, 0.0) + n2
        factors = {
            gi: abadi(np.sqrt(n2), th[gi]) * mask for gi, n2 in g_norms2.items()
        }
        out = {
            k: np.einsum("b...,b->...", v, factors[policy.group_of(k)])
            for k, v in flat.items()
        }
    else:
        if isinstance(policy, FixedPolicy):
            c = abadi(norms, policy.clip_norm)
        elif isinstance(policy, AutomaticPolicy):
            c = 1.0 / (norms + policy.gamma)
        elif isinstance(policy, QuantilePolicy):
            c = abadi(norms, float(pstate["clip_norm"]))
        else:
            raise AssertionError(policy)
        c = c * mask
        out = {k: np.einsum("b...,b->...", v, c) for k, v in flat.items()}
    return out


def _policies():
    return {
        "fixed": FixedPolicy(clip_norm=0.3),
        "automatic": AutomaticPolicy(gamma=0.01),
        # non-default state R: proves the factors read the STATE, not R0
        "quantile": QuantilePolicy(init_clip_norm=0.37),
        "per_layer": PerLayerPolicy(groups=("emb", "l1"), clip_norm=0.3),
    }


@pytest.mark.parametrize("name", ["fixed", "automatic", "quantile", "per_layer"])
def test_policy_exactness_across_executors(name):
    """Acceptance oracle: every policy x every executor family == naive."""
    m, params, batch = _setup()
    policy = _policies()[name]
    pstate = policy.init_state()
    ref = _naive_reference(policy, pstate, m, params, batch)
    for mode in MODES:
        fn = jax.jit(dp_value_and_clipped_grad(
            m.loss_with_ctx, ClipConfig(mode=mode, clip_norm=0.3, policy=policy)
        ))
        _, g, aux = fn(params, batch, pstate)
        flat = flatten_dict(g)
        for path, want in ref.items():
            err = float(np.max(np.abs(np.asarray(flat[path], np.float64) - want)))
            assert err < 5e-5, (name, mode, path, err)
        # masked samples contribute zero factors everywhere
        assert float(aux["clip_factors"][2]) == 0.0, (name, mode)


@pytest.mark.parametrize("name", ["fixed", "automatic", "quantile", "per_layer"])
@pytest.mark.parametrize("mode", ["mixed_ghost", "bk_mixed"])
def test_flipped_plan_changes_no_policy_output(name, mode):
    """Acceptance: an adversarially flipped-branch plan is invisible to every
    policy — the plan moves cost, the policy moves factors, never together."""
    m, params, batch = _setup()
    policy = _policies()[name]
    pstate = policy.init_state()
    metas = discover_meta(m.loss_with_ctx, params, batch)

    def flip(branch):
        return "instantiate" if branch == "ghost" else "ghost"

    flipped = ClipPlan(
        fingerprint=shape_fingerprint(metas),
        device=device_string(),
        branches=tuple(
            (n, flip(decide(mm, mode="mixed_ghost")))
            for n, mm in sorted(metas.items()) if mm.kind == "matmul"
        ),
        bk_branches=tuple(
            (n, flip(decide(mm, mode="bk_mixed")))
            for n, mm in sorted(metas.items()) if mm.kind == "matmul"
        ),
        policy_fingerprint=policy.fingerprint(),
    )
    cfg = dict(mode=mode, clip_norm=0.3, policy=policy)
    l1, g1, a1 = dp_value_and_clipped_grad(
        m.loss_with_ctx, ClipConfig(**cfg)
    )(params, batch, pstate)
    l2, g2, a2 = dp_value_and_clipped_grad(
        m.loss_with_ctx, ClipConfig(**cfg, plan=flipped)
    )(params, batch, pstate)
    assert float(l1) == float(l2)
    assert jnp.allclose(a1["clip_factors"], a2["clip_factors"], atol=1e-6)
    assert max_tree_diff(g1, g2) < 1e-5, (name, mode)


# ------------------------------------------------------------- policies --
def test_automatic_sensitivity_bounds_contributions():
    """||C_i g_i|| <= sensitivity() == 1 for automatic clipping."""
    m, params, batch = _setup(mask=(1.0, 1.0, 1.0, 1.0))
    policy = AutomaticPolicy(gamma=0.01)
    fn = dp_value_and_clipped_grad(
        m.loss_with_ctx, ClipConfig(mode="mixed_ghost", policy=policy)
    )
    _, _, aux = fn(params, batch, policy.init_state())
    contrib = aux["clip_factors"] * aux["per_sample_norms"]
    assert float(jnp.max(contrib)) <= policy.sensitivity(policy.init_state()) + 1e-6


def test_quantile_update_tracks_target_quantile():
    """Noise-free updates converge R to the target quantile of the norms."""
    policy = QuantilePolicy(
        target_quantile=0.75, lr=0.3, release_sigma=0.0, init_clip_norm=1.0
    )
    norms = jnp.asarray([0.5, 1.5, 2.5, 3.5, 4.5, 5.5, 6.5, 7.5])
    state = policy.init_state()
    for _ in range(200):
        state, ev = policy.update(state, norms)
    assert not ev.spends  # sigma=0: free, and NOT differentially private
    r = float(state["clip_norm"])
    # the 0.75 quantile of 8 samples sits between the 6th and 7th value
    assert 5.5 < r < 7.5, r
    assert int(state["step"]) == 200


def test_quantile_update_respects_mask():
    """Masked-out samples must not count as 'below R' (they have norm 0)."""
    policy = QuantilePolicy(target_quantile=0.5, lr=0.2, release_sigma=0.0)
    norms = jnp.asarray([10.0, 10.0, 0.0, 0.0])
    mask = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    s0 = policy.init_state()
    s_masked, _ = policy.update(s0, norms, mask=mask)
    s_unmasked, _ = policy.update(s0, norms)
    # with the mask, nothing is below R=1 -> b=0 -> R grows by exp(lr*q);
    # without it the two zero-norm fakes push b to 0.5 -> R stays put
    assert float(s_masked["clip_norm"]) > float(s_unmasked["clip_norm"])


def test_quantile_needs_key_when_noised():
    policy = QuantilePolicy(release_sigma=1.0)
    with pytest.raises(ValueError):
        policy.update(policy.init_state(), jnp.ones((4,)))


@pytest.mark.parametrize("mode", ["bk_mixed", "mixed_ghost", "vmap"])
def test_per_layer_group_split_raises_at_trace(mode):
    """A group boundary through a tap's (weight, bias) pair must raise — on
    every executor family, including the vmap oracle (whose per-leaf norms
    could otherwise silently accept semantics no other mode reproduces)."""
    m, params, batch = _setup()
    policy = PerLayerPolicy(groups=("l1/w",), clip_norm=0.3)
    fn = dp_value_and_clipped_grad(
        m.loss_with_ctx, ClipConfig(mode=mode, policy=policy)
    )
    with pytest.raises(ValueError, match="different groups"):
        fn(params, batch, policy.init_state())


def test_per_layer_threshold_budget():
    """sum R_g^2 == R^2 (equal split incl. catch-all), sensitivity == R."""
    policy = PerLayerPolicy(groups=("a", "b"), clip_norm=2.0)
    st = policy.init_state()
    th = np.asarray(st["thresholds"])
    assert th.shape == (3,)  # a, b, catch-all
    assert abs(float((th ** 2).sum()) - 4.0) < 1e-6
    assert abs(float(policy.sensitivity(st)) - 2.0) < 1e-5


def test_make_policy_filters_kwargs():
    p = make_policy("automatic", clip_norm=9.0, gamma=0.5, groups=("x",))
    assert isinstance(p, AutomaticPolicy) and p.gamma == 0.5
    with pytest.raises(ValueError, match="unknown clip policy"):
        make_policy("nope")


# ----------------------------------------------------------- accounting --
def test_quantile_epsilon_matches_manual_composition():
    """Acceptance: reported epsilon == manual {gradient + release} RDP."""
    from repro.core.accountant import (
        DEFAULT_ALPHAS,
        eps_from_rdp,
        rdp_subsampled_gaussian,
    )
    from repro.core.engine import PrivacyEngine

    def loss(params, batch, ctx):
        raise NotImplementedError  # accounting only

    kw = dict(loss_with_ctx=loss, batch_size=8, sample_size=10_000,
              steps=64, max_grad_norm=1.0, noise_multiplier=1.3)
    eng = PrivacyEngine(**kw, clip_policy=QuantilePolicy(release_sigma=0.7))
    fixed = PrivacyEngine(**kw)
    eps, delta = eng.privacy_spent(steps=64)
    q = eng.sampling_rate
    rdp = 64 * (rdp_subsampled_gaussian(q, 1.3, DEFAULT_ALPHAS)
                + rdp_subsampled_gaussian(q, 0.7, DEFAULT_ALPHAS))
    assert eps == pytest.approx(eps_from_rdp(rdp, DEFAULT_ALPHAS, delta)[0], abs=1e-12)
    # strictly more than the gradient mechanism alone
    assert eps > fixed.privacy_spent(steps=64)[0]
    # the step-recorded path composes identically
    eng.record_step(64)
    assert eng.accountant.get_epsilon(delta) == pytest.approx(eps, abs=1e-9)
    # a release-free quantile policy spends exactly like fixed
    free = PrivacyEngine(**kw, clip_policy=QuantilePolicy(release_sigma=0.0))
    assert free.privacy_spent(steps=64)[0] == pytest.approx(
        fixed.privacy_spent(steps=64)[0], abs=1e-12
    )


def test_target_epsilon_bisection_composes_release():
    """--target-epsilon convenience: sigma lands the TOTAL spend (gradient +
    quantile release) on the target, instead of needing a hand-picked sigma
    with headroom guessed for the release."""
    from repro.core.engine import PrivacyEngine

    def loss(params, batch, ctx):
        raise NotImplementedError

    kw = dict(loss_with_ctx=loss, batch_size=8, sample_size=10_000,
              steps=64, max_grad_norm=1.0, target_epsilon=2.0)
    eng_q = PrivacyEngine(**kw, clip_policy=QuantilePolicy(release_sigma=0.7))
    eng_f = PrivacyEngine(**kw)
    # the release costs budget, so the gradient mechanism must be noisier
    assert eng_q.noise_multiplier > eng_f.noise_multiplier
    eps_q, _ = eng_q.privacy_spent(steps=64)
    assert eps_q <= 2.0 + 1e-6  # total spend (incl. release) meets the target


# ------------------------------------------------- checkpoint round-trip --
def _tiny_train(policy, steps, tmp_path=None, save_at=None, resume_from=None):
    """Run the real jitted train step; optionally snapshot/restore."""
    from repro.checkpoint.checkpointer import restore_checkpoint, save_checkpoint
    from repro.launch.steps import DPTrainConfig, make_train_state, make_train_step
    from repro.optim import adam, warmup_cosine

    m = _MLPModel()
    opt = adam()
    dp = DPTrainConfig(
        clipping_mode="bk_mixed", clip_norm=1.0, noise_multiplier=0.8,
        logical_batch=4, policy=policy,
    )
    step_fn = jax.jit(make_train_step(m, opt, warmup_cosine(1e-3, 2, 10), dp))
    if resume_from is not None:
        _, state = restore_checkpoint(resume_from)
        start = int(state["step"])
    else:
        state = make_train_state(m, jax.random.PRNGKey(0), opt, policy)
        start = 0
    for i in range(start, steps):
        batch = lm_batch(jax.random.fold_in(jax.random.PRNGKey(7), i), 4, 6, 17)
        batch["mask"] = jnp.ones((4,))
        state, _ = step_fn(state, batch)
        if save_at is not None and i + 1 == save_at:
            save_checkpoint(tmp_path, i + 1, state)
    return state


@pytest.mark.parametrize("name", ["quantile", "per_layer"])
def test_policy_state_checkpoint_roundtrip_and_resume(name, tmp_path):
    """Acceptance: quantile R / per-layer thresholds survive save/restore
    and a resumed run reproduces the uninterrupted trajectory bit-exactly."""
    policies = {
        "quantile": lambda: QuantilePolicy(
            target_quantile=0.6, release_sigma=0.4, init_clip_norm=1.0
        ),
        "per_layer": lambda: PerLayerPolicy(groups=("emb",), clip_norm=1.0),
    }
    straight = _tiny_train(policies[name](), steps=4)
    _tiny_train(policies[name](), steps=2, tmp_path=tmp_path, save_at=2)
    resumed = _tiny_train(policies[name](), steps=4, resume_from=tmp_path)
    # the policy state itself: bit-identical across the save/restore seam
    for k, v in flatten_dict(straight["policy"]).items():
        rv = flatten_dict(resumed["policy"])[k]
        assert np.array_equal(np.asarray(v), np.asarray(rv)), (name, k)
    # and it actually adapted (stateful policies must not be frozen)
    if name == "quantile":
        assert float(straight["policy"]["clip_norm"]) != 1.0
    assert int(straight["policy"]["step"]) == 4
    # the whole trajectory (params included) is reproduced
    assert max_tree_diff(straight["params"], resumed["params"]) == 0.0


# ------------------------------------------------------ plan / consensus --
def test_policy_fingerprint_changes_consensus_hash():
    base = ClipPlan(fingerprint="ab" * 8, device=device_string())
    stamped = dataclasses.replace(base, policy_fingerprint="quantile:q=0.5")
    other = dataclasses.replace(base, policy_fingerprint="fixed:R=1")
    assert base.consensus_hash() != stamped.consensus_hash()
    assert stamped.consensus_hash() != other.consensus_hash()
    # round-trips through JSON
    assert ClipPlan.from_json(stamped.to_json()).policy_fingerprint == "quantile:q=0.5"


def test_fleet_rejects_mixed_policy_fingerprints():
    from repro.tuner.consensus import PlanConsensusError, RankReport, agree

    m, params, batch = _setup()
    metas = discover_meta(m.loss_with_ctx, params, batch)
    fp = shape_fingerprint(metas)
    plan = ClipPlan(
        fingerprint=fp, device=device_string(),
        policy_fingerprint="quantile:q=0.5",
    )
    mixed = [
        RankReport(0, device_string(), fp, plan.to_json(), None,
                   policy="quantile:q=0.5"),
        RankReport(1, device_string(), fp, None, None, policy="fixed:R=1"),
    ]
    with pytest.raises(PlanConsensusError, match="clipping-policy"):
        agree(mixed)
    uniform = [
        RankReport(0, device_string(), fp, plan.to_json(), None,
                   policy="quantile:q=0.5"),
        RankReport(1, device_string(), fp, None, None, policy="quantile:q=0.5"),
    ]
    adopted = agree(uniform)
    assert adopted.policy_fingerprint == "quantile:q=0.5"
    assert adopted.agreed_ranks == 2


def test_verify_adopted_rejects_foreign_policy_stamp():
    from repro.tuner.consensus import PlanConsensusError, verify_adopted

    m, params, batch = _setup()
    metas = discover_meta(m.loss_with_ctx, params, batch)
    plan = ClipPlan(
        fingerprint=shape_fingerprint(metas), device=device_string(),
        policy_fingerprint="per_layer:groups=emb|",
    )
    verify_adopted(plan, metas)  # no expectation: fine
    verify_adopted(plan, metas, policy_fingerprint="per_layer:groups=emb|")
    with pytest.raises(PlanConsensusError, match="policy"):
        verify_adopted(plan, metas, policy_fingerprint="fixed:R=1")
    # unstamped (pre-v4) plans are accepted under any policy
    bare = dataclasses.replace(plan, policy_fingerprint="")
    verify_adopted(bare, metas, policy_fingerprint="fixed:R=1")


def test_engine_tune_stamps_policy_fingerprint(tmp_path):
    from repro.core.engine import PrivacyEngine
    from repro.tuner.measure import MeasureConfig

    m, params, batch = _setup()
    policy = QuantilePolicy(target_quantile=0.8)
    eng = PrivacyEngine(
        loss_with_ctx=m.loss_with_ctx, batch_size=4, sample_size=1000,
        steps=10, max_grad_norm=1.0, noise_multiplier=1.0,
        clip_policy=policy,
    )
    plan = eng.tune(
        params, batch, arch="mlp-pol", search_max_batch=False,
        measure=MeasureConfig(repeats=1, warmup=1),
        plan_path=str(tmp_path / "p.json"), use_cache=False,
    )
    assert plan.policy_fingerprint == policy.fingerprint()
    assert ClipPlan.load(str(tmp_path / "p.json")).policy_fingerprint == \
        policy.fingerprint()
    # consensus on a single process: the agreed plan keeps the stamp and
    # certifies under the same policy
    plan2 = eng.tune(
        params, batch, arch="mlp-pol", search_max_batch=False,
        measure=MeasureConfig(repeats=1, warmup=1),
        plan_path=str(tmp_path / "p.json"), consensus=True,
    )
    assert plan2.policy_fingerprint == policy.fingerprint()
    assert plan2.agreed_ranks == 1


# ------------------------- quantile denominator under Poisson subsampling --
def test_quantile_denominator_is_static_under_poisson_mask():
    """b_t divides by the STATIC batch shape, never the (private) mask sum.

    With 3 of 8 samples Poisson-selected and every selected norm below R,
    a mask-sum denominator would say b=1.0 (quantile reached); the
    data-independent denominator says b=3/8.  The update must match the
    closed form exactly.
    """
    q, lr = 0.5, 0.2
    policy = QuantilePolicy(target_quantile=q, lr=lr, release_sigma=0.0,
                            init_clip_norm=1.0)
    norms = jnp.asarray([0.1, 0.2, 0.3, 9.0, 9.0, 9.0, 9.0, 9.0])
    mask = jnp.asarray([1.0, 1.0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    state, _ = policy.update(policy.init_state(), norms, mask=mask)
    expected = 1.0 * np.exp(-lr * (3.0 / 8.0 - q))
    np.testing.assert_allclose(float(state["clip_norm"]), expected, rtol=1e-6)


@pytest.mark.parametrize("physical", [1, 2])
def test_quantile_empty_poisson_round_at_tiny_batch(physical):
    """A tiny physical batch where Poisson sampled NOTHING: b=0 and R grows
    by exactly exp(lr*q) — no NaN from an empty-mask denominator."""
    q, lr = 0.6, 0.25
    policy = QuantilePolicy(target_quantile=q, lr=lr, release_sigma=0.0,
                            init_clip_norm=2.0)
    norms = jnp.full((physical,), 0.5)  # below R, but masked out
    mask = jnp.zeros((physical,))
    state, _ = policy.update(policy.init_state(), norms, mask=mask)
    r = float(state["clip_norm"])
    assert np.isfinite(r)
    np.testing.assert_allclose(r, 2.0 * np.exp(lr * q), rtol=1e-6)


def test_quantile_scattered_logical_batch_matches_direct_update():
    """The accumulation path scatters per-microbatch norms/masks into one
    flat logical-batch buffer (launch.steps.make_accum_microstep) and
    updates once; the result must equal a direct update on the concatenated
    batch, in any microbatch order (the count is permutation-invariant)."""
    policy = QuantilePolicy(target_quantile=0.5, lr=0.2, release_sigma=0.0,
                            init_clip_norm=1.0)
    key = jax.random.PRNGKey(3)
    norms = jax.random.uniform(key, (8,), minval=0.0, maxval=2.0)
    mask = (jax.random.uniform(jax.random.PRNGKey(4), (8,)) < 0.4).astype(
        jnp.float32
    )
    s0 = policy.init_state()
    direct, _ = policy.update(s0, norms, mask=mask)
    # scatter microbatches of 2 into the flat buffers, reversed order
    flat_n = jnp.zeros((8,))
    flat_m = jnp.zeros((8,))
    for i in reversed(range(4)):
        off = (i * 2,)
        flat_n = jax.lax.dynamic_update_slice(flat_n, norms[i * 2:i * 2 + 2], off)
        flat_m = jax.lax.dynamic_update_slice(flat_m, mask[i * 2:i * 2 + 2], off)
    scattered, _ = policy.update(s0, flat_n, mask=flat_m)
    np.testing.assert_allclose(
        float(scattered["clip_norm"]), float(direct["clip_norm"]), rtol=1e-7
    )
