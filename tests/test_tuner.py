"""repro.tuner: the measured-cost ClipPlan and its decision-override plumbing.

Covers the Eq-(4.1) boundary cases, the Remark-4.1 time variant, plan JSON
round-trip + stale-plan rejection, the max-batch search, and the subsystem's
correctness oracle: clipped gradients under a (even adversarially flipped)
plan must match the analytic ``mixed_ghost`` exactly — the branch choice is
pure cost, never math.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core.clipping import ClipConfig, discover_meta, dp_value_and_clipped_grad
from repro.core.decision import decide, ghost_is_cheaper
from repro.core.taps import Ctx, TapMeta
from repro.nn.module import Dense
from repro.nn.xlstm import SLSTMBlock
from repro.tuner import (
    ClipPlan,
    MeasureConfig,
    build_plan,
    derive_accumulation,
    device_string,
    find_max_physical_batch,
    max_batch_by_memory,
    remeasure_at_batch,
    shape_fingerprint,
)

from helpers import max_tree_diff


def _meta(kind="matmul", T=8, D=16, p=4, batch=2):
    return TapMeta(
        kind=kind, T=T, D=D, p=p, s_shape=(batch, T, p), s_dtype=jnp.float32,
        param_path="w", batch_size=batch,
    )


# ---------------------------------------------------------------- decision --
def test_eq41_tie_prefers_instantiate():
    # 2T^2 == pD is NOT strictly cheaper: the paper's rule picks instantiate.
    T, p, D = 4, 2, 16
    assert 2 * T * T == p * D
    assert not ghost_is_cheaper(T, D, p, by="space")
    assert decide(_meta(T=T, D=D, p=p), mode="mixed_ghost") == "instantiate"


def test_remark41_time_variant_differs_from_space():
    # T=2, D=16, p=1: space rule 2T^2=8 < pD=16 -> ghost, but the time rule
    # 2T^2(D+p+1) = 144 >= 2(T+1)pD = 96 -> instantiate.
    assert ghost_is_cheaper(2, 16, 1, by="space")
    assert not ghost_is_cheaper(2, 16, 1, by="time")
    m = _meta(T=2, D=16, p=1)
    assert decide(m, mode="mixed_ghost", by="space") == "ghost"
    assert decide(m, mode="mixed_ghost", by="time") == "instantiate"


def test_plan_override_wins_over_analytic_rule():
    m = _meta(T=1, D=64, p=64)  # analytic: 2 < 4096 -> ghost
    assert decide(m, mode="mixed_ghost") == "ghost"
    assert decide(m, mode="mixed_ghost", override="instantiate") == "instantiate"
    assert decide(m, mode="mixed_ghost", override="ghost") == "ghost"
    with pytest.raises(ValueError):
        decide(m, mode="mixed_ghost", override="banana")


def test_override_never_wins_over_forced_kinds():
    # embedding/scale taps have exactly one viable norm computation
    emb = _meta(kind="embedding")
    assert decide(emb, override="instantiate") == "ghost"
    scale = _meta(kind="scale")
    assert decide(scale, override="ghost") == "instantiate"


def test_override_never_wins_over_reference_modes():
    # the fixed-branch modes are gone (a plan forcing every tap expresses
    # them), so no override can ride on their names: decide refuses them
    m = _meta(T=1, D=64, p=64)
    with pytest.raises(ValueError, match="unknown clipping mode"):
        decide(m, mode="ghost", override="instantiate")
    with pytest.raises(ValueError, match="unknown clipping mode"):
        decide(m, mode="fastgradclip", override="ghost")


def test_bk_branch_rule_is_bank_size_driven():
    # lm_head-like: T small, pD huge -> the (a, g) book is far smaller than
    # per-sample gradient instantiation
    m = _meta(T=1, D=64, p=4096)
    assert decide(m, mode="bk_mixed") == "ghost"
    # conv-like: T large, pD small -> bank the per-sample gradients
    m = _meta(T=1024, D=27, p=32)
    assert decide(m, mode="bk_mixed") == "instantiate"
    # the two rules legitimately disagree: Eq 4.1 only weighs the norm
    # computation (2T^2 vs pD); bk also has to HOLD the book, so a tap can
    # be ghost-cheap to norm yet psg-cheap to bank
    m = _meta(T=16, D=32, p=32)
    assert decide(m, mode="mixed_ghost") == "ghost"  # 2T^2 = 512 < pD = 1024
    # book = T(D+p) + 2T^2 = 1024 + 512 = 1536 >= pD = 1024
    assert decide(m, mode="bk_mixed") == "instantiate"
    # raw-activation awareness: a stride-2 conv's raw input is ~2.25x smaller
    # than its unfolded patches, flipping the book back to affordable
    conv_meta = dataclasses.replace(
        _meta(T=64, D=576, p=64, batch=2),
        a_shape=(2, 16, 16, 64), a_dtype=jnp.float32,
    )
    # book = 16*16*64 + 64*64 + 2*64^2 = 28672 < pD = 36864
    assert decide(conv_meta, mode="bk_mixed") == "ghost"
    # unfolded-size fallback (no recorded activation shape): instantiate
    # book = 64*576 + 64*64 + 8192 = 49152 >= 36864
    no_a = _meta(T=64, D=576, p=64)
    assert decide(no_a, mode="bk_mixed") == "instantiate"


def test_bk_override_wins_and_stays_exact_branchwise():
    m = _meta(T=1, D=64, p=4096)
    assert decide(m, mode="bk_mixed", override="instantiate") == "instantiate"
    with pytest.raises(ValueError):
        decide(m, mode="bk_mixed", override="banana")


# -------------------------------------------------------------------- plan --
def _tiny_metas():
    return {
        "a/out": _meta(T=8, D=16, p=4),
        "b/out": _meta(T=2, D=32, p=32),
        "emb/out": _meta(kind="embedding", T=8, D=1, p=16),
    }


def test_clipplan_json_round_trip(tmp_path):
    metas = _tiny_metas()
    plan = ClipPlan(
        fingerprint=shape_fingerprint(metas),
        device=device_string(),
        branches=(("a/out", "instantiate"), ("b/out", "ghost")),
        bk_branches=(("a/out", "instantiate"), ("b/out", "instantiate")),
        physical_batch=64,
        logical_batch=256,
        accumulation_steps=4,
        measured_at_physical=True,
        arch="tiny",
        timings=(("a/out", 10.0, 5.0, 9.0, 6.0, 20.0),
                 ("b/out", 3.0, 7.0, 8.0, 4.0, 12.0)),
    )
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = ClipPlan.load(path)
    assert loaded == plan
    assert loaded.branch_map() == {"a/out": "instantiate", "b/out": "ghost"}
    assert loaded.branch_map("bk_mixed") == {
        "a/out": "instantiate", "b/out": "instantiate"
    }
    # the artifact is plain JSON, inspectable by other tooling
    raw = json.loads(open(path).read())
    assert raw["physical_batch"] == 64
    assert raw["measured_at_physical"] is True


def test_clipplan_mode_costs_and_recommendation():
    # mixed_ghost: min(10,5)+20 + min(3,7)+12 = 40; bk: min(9,6)+min(8,4)=10
    plan = ClipPlan(
        fingerprint="f", device="d",
        timings=(("a", 10.0, 5.0, 9.0, 6.0, 20.0),
                 ("b", 3.0, 7.0, 8.0, 4.0, 12.0)),
    )
    assert plan.mode_cost_us("mixed_ghost") == 40.0
    assert plan.mode_cost_us("bk_mixed") == 10.0
    assert plan.recommended_mode() == "bk_mixed"
    assert ClipPlan(fingerprint="f", device="d").recommended_mode() == "mixed_ghost"


def test_clipplan_rejects_bad_json():
    with pytest.raises(ValueError):
        ClipPlan.from_json(json.dumps({"fingerprint": "x", "device": "y",
                                       "version": 99}))
    # pre-three-way (v1) artifacts are stale by construction: their branch
    # maps know nothing about the bk bank decision
    with pytest.raises(ValueError):
        ClipPlan.from_json(json.dumps({"fingerprint": "x", "device": "y",
                                       "version": 1}))
    with pytest.raises(ValueError):
        ClipPlan.from_json(json.dumps({
            "fingerprint": "x", "device": "y", "version": 2,
            "branches": [["a", "banana"]],
        }))
    with pytest.raises(ValueError):
        ClipPlan.from_json(json.dumps({
            "fingerprint": "x", "device": "y", "version": 2,
            "bk_branches": [["a", "banana"]],
        }))


def test_stale_plan_rejected_falls_back_to_analytic():
    metas = _tiny_metas()
    good = ClipPlan(
        fingerprint=shape_fingerprint(metas), device=device_string(),
        branches=(("a/out", "instantiate"),),
        bk_branches=(("a/out", "ghost"), ("b/out", "ghost")),
    )
    assert good.overrides_for(metas) == {"a/out": "instantiate"}
    # mode-specific maps: bk_mixed reads the bank branches
    assert good.overrides_for(metas, mode="bk_mixed") == {
        "a/out": "ghost", "b/out": "ghost"
    }

    # different shapes (stale fingerprint) -> no overrides
    stale = dataclasses.replace(good, fingerprint="deadbeefdeadbeef")
    assert stale.overrides_for(metas) == {}

    # different device -> no overrides
    wrong_dev = dataclasses.replace(good, device="tpu:TPU v9")
    assert wrong_dev.overrides_for(metas) == {}

    # fingerprint tracks shapes: changing one tap's D changes it
    other = dict(metas, **{"a/out": _meta(T=8, D=32, p=4)})
    assert shape_fingerprint(other) != shape_fingerprint(metas)
    # but not the batch size (plans transfer across physical batch)
    rebatched = dict(metas, **{"a/out": _meta(T=8, D=16, p=4, batch=64)})
    assert shape_fingerprint(rebatched) == shape_fingerprint(metas)


# --------------------------------------------------------------- max batch --
def test_find_max_physical_batch_is_exact():
    for threshold in (1, 2, 37, 64, 100):
        calls = []

        def fits(b, t=threshold):
            calls.append(b)
            return b <= t

        assert find_max_physical_batch(fits, hi_cap=128) == min(threshold, 128)
    assert find_max_physical_batch(lambda b: False, hi_cap=128) == 0
    assert find_max_physical_batch(lambda b: True, hi_cap=128) == 128


def test_derive_accumulation_invariants():
    for logical, max_phys in [(256, 96), (256, 64), (8, 64), (7, 2), (1, 1)]:
        physical, steps = derive_accumulation(logical, max_phys)
        assert physical <= max_phys
        assert physical * steps >= logical
        # steps is minimal: one fewer microstep cannot cover the logical batch
        assert (steps - 1) * max_phys < logical
    with pytest.raises(ValueError):
        derive_accumulation(0, 4)
    with pytest.raises(ValueError):
        derive_accumulation(4, 0)


# --------------------------------------------- end-to-end correctness oracle --
class TwoLayer:
    """Tiny model with one ghost-leaning and one instantiate-leaning tap."""

    def __init__(self):
        self.f1 = Dense("f1", 12, 8)
        self.f2 = Dense("f2", 8, 4)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"f1": self.f1.init(k1), "f2": self.f2.init(k2)}

    def loss_with_ctx(self, params, batch, ctx: Ctx):
        h = jax.nn.relu(self.f1(params["f1"], batch["x"], ctx.scope("f1")))
        out = self.f2(params["f2"], h, ctx.scope("f2"))
        return jnp.mean((out - batch["y"]) ** 2, axis=(1, 2))


class WithSLSTM(TwoLayer):
    """TwoLayer around an sLSTM block, whose recurrent weight is a
    ``late=True`` tap: its norm and book-keeping gradient go through the
    fused engine's explicit channel (``ghost.tap_norm_sq`` and
    ``ghost.tap_weighted_grads`` on the recorded activation)."""

    def __init__(self):
        super().__init__()
        self.sl = SLSTMBlock("sl", 8, n_heads=2)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {**super().init(k1), "sl": self.sl.init(k2)}

    def loss_with_ctx(self, params, batch, ctx: Ctx):
        h = jax.nn.relu(self.f1(params["f1"], batch["x"], ctx.scope("f1")))
        h, _ = self.sl(params["sl"], h, ctx.scope("sl"))
        out = self.f2(params["f2"], h, ctx.scope("f2"))
        return jnp.mean((out - batch["y"]) ** 2, axis=(1, 2))


def _two_layer_setup(model_cls=TwoLayer):
    model = model_cls()
    params = model.init(jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "x": jax.random.normal(k1, (4, 6, 12)),
        "y": jax.random.normal(k2, (4, 6, 4)),
    }
    return model, params, batch


@pytest.mark.parametrize("mode,model_cls", [
    ("mixed_ghost", TwoLayer), ("mixed_ghost", WithSLSTM),
    ("bk_mixed", TwoLayer), ("bk_mixed", WithSLSTM),
], ids=["mixed_ghost", "mixed_ghost-late", "bk_mixed", "bk_mixed-late"])
def test_plan_changes_branch_not_math(mode, model_cls):
    """Clipped grads under an adversarially flipped three-way plan == analytic.

    Both branch maps are inverted: the norm branch of the second-backward
    modes AND the bank branch of book-keeping.  Either way the math is
    identical — the plan moves cost, never results.
    """
    model, params, batch = _two_layer_setup(model_cls)
    metas = discover_meta(model.loss_with_ctx, params, batch)

    def flip(branch):
        return "instantiate" if branch == "ghost" else "ghost"

    flipped = ClipPlan(
        fingerprint=shape_fingerprint(metas),
        device=device_string(),
        branches=tuple(
            (n, flip(decide(m, mode="mixed_ghost")))
            for n, m in sorted(metas.items()) if m.kind == "matmul"
        ),
        bk_branches=tuple(
            (n, flip(decide(m, mode="bk_mixed")))
            for n, m in sorted(metas.items()) if m.kind == "matmul"
        ),
    )
    f_analytic = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig(mode=mode))
    f_plan = dp_value_and_clipped_grad(
        model.loss_with_ctx, ClipConfig(mode=mode, plan=flipped)
    )
    l1, g1, a1 = f_analytic(params, batch)
    l2, g2, a2 = f_plan(params, batch)
    assert float(l1) == float(l2)
    assert jnp.allclose(a1["per_sample_norms"], a2["per_sample_norms"], atol=1e-5)
    assert max_tree_diff(g1, g2) < 1e-5


@pytest.mark.parametrize("mode,model_cls", [
    ("mixed_ghost", TwoLayer), ("bk_mixed", TwoLayer), ("bk_mixed", WithSLSTM),
], ids=["mixed_ghost", "bk_mixed", "bk_mixed-late"])
def test_plan_kernel_choice_changes_no_output(mode, model_cls):
    """Flipping the plan-recorded kernel impl (v5 ``kernels``) re-routes the
    hot ops through the other implementation without changing any output —
    the acceptance oracle for the dispatch layer."""
    model, params, batch = _two_layer_setup(model_cls)
    metas = discover_meta(model.loss_with_ctx, params, batch)
    from repro.tuner.measure import KERNEL_OPS_BY_KIND

    def plan_with(impl):
        return ClipPlan(
            fingerprint=shape_fingerprint(metas),
            device=device_string(),
            kernels=tuple(
                (n, op, impl)
                for n, m in sorted(metas.items())
                for op in KERNEL_OPS_BY_KIND.get(m.kind, ())
            ),
        )

    outs = {}
    for impl in ("xla", "pallas"):
        fn = dp_value_and_clipped_grad(
            model.loss_with_ctx, ClipConfig(mode=mode, plan=plan_with(impl))
        )
        outs[impl] = fn(params, batch)
    l_x, g_x, aux_x = outs["xla"]
    l_p, g_p, aux_p = outs["pallas"]
    assert jnp.allclose(l_x, l_p, rtol=1e-6)
    assert jnp.allclose(
        aux_x["per_sample_norms"], aux_p["per_sample_norms"], atol=1e-5
    )
    assert max_tree_diff(g_x, g_p) < 1e-5


def test_plan_v5_kernels_round_trip_and_staleness(tmp_path):
    metas = _tiny_metas()
    plan = ClipPlan(
        fingerprint=shape_fingerprint(metas),
        device=device_string(),
        kernels=(("a/out", "ghost_norm", "xla"),
                 ("a/out", "psg_contract", "xla"),
                 ("emb/out", "embedding_ghost_norm", "xla")),
    )
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = ClipPlan.load(path)
    assert loaded == plan
    assert loaded.kernel_map() == {
        "a/out": {"ghost_norm": "xla", "psg_contract": "xla"},
        "emb/out": {"embedding_ghost_norm": "xla"},
    }
    assert loaded.kernels_for(metas) == loaded.kernel_map()
    # stale fingerprint or wrong device -> {} (dispatch backend default)
    stale = dataclasses.replace(loaded, fingerprint="deadbeefdeadbeef")
    assert stale.kernels_for(metas) == {}
    wrong_dev = dataclasses.replace(loaded, device="tpu:TPU v9")
    assert wrong_dev.kernels_for(metas) == {}
    # RATIFYING a fleet agreement is enough for branch overrides but NOT
    # for the kernel map: impls are backend-specific, and a pallas winner
    # measured on the fleet's TPU kind must not trace the interpreter on
    # the ratifying kinds
    ratified = dataclasses.replace(
        loaded, device="tpu:TPU v9", devices=(device_string(),),
        branches=(("a/out", "ghost"),),
        kernels=(("a/out", "ghost_norm", "pallas"),),
    )
    assert ratified.overrides_for(metas) == {"a/out": "ghost"}
    assert ratified.kernels_for(metas) == {}
    # the kernel map is covered by the consensus hash: a fleet cannot mix
    flipped = dataclasses.replace(
        loaded, kernels=(("a/out", "ghost_norm", "pallas"),) + loaded.kernels[1:]
    )
    assert flipped.consensus_hash() != loaded.consensus_hash()
    # invalid impls and unknown ops are rejected at parse time — a typo'd
    # op would otherwise load cleanly and silently never take effect
    bad = json.loads(plan.to_json())
    bad["kernels"] = [["a/out", "ghost_norm", "banana"]]
    with pytest.raises(ValueError):
        ClipPlan.from_json(json.dumps(bad))
    bad["kernels"] = [["a/out", "ghost_nrm", "pallas"]]
    with pytest.raises(ValueError, match="unknown kernel op"):
        ClipPlan.from_json(json.dumps(bad))
    # v4 artifacts (no kernels key) migrate with an empty map
    v4 = json.loads(plan.to_json())
    del v4["kernels"]
    v4["version"] = 4
    assert ClipPlan.from_json(json.dumps(v4)).kernels == ()


def test_build_plan_records_kernel_choices():
    model, params, batch = _two_layer_setup()
    metas = discover_meta(model.loss_with_ctx, params, batch)
    from repro.kernels import dispatch
    from repro.tuner.measure import KERNEL_OPS_BY_KIND

    plan = build_plan(
        metas, measure=MeasureConfig(repeats=1, warmup=1), arch="twolayer"
    )
    kmap = plan.kernel_map()
    expected_taps = {
        n for n, m in metas.items() if m.kind in KERNEL_OPS_BY_KIND
    }
    assert set(kmap) == expected_taps
    for n, ks in kmap.items():
        assert set(ks) == set(KERNEL_OPS_BY_KIND[metas[n].kind])
        for impl in ks.values():
            assert impl in dispatch.available_impls()


def test_measured_plan_round_trips_through_engine(tmp_path):
    """build_plan -> save -> ClipConfig(plan=...) produces analytic-equal grads."""
    model, params, batch = _two_layer_setup()
    metas = discover_meta(model.loss_with_ctx, params, batch)
    plan = build_plan(
        metas, measure=MeasureConfig(repeats=1, warmup=1), arch="twolayer"
    )
    assert set(plan.branch_map()) == {
        n for n, m in metas.items() if m.kind == "matmul"
    }
    path = str(tmp_path / "plan.json")
    plan.save(path)
    plan = ClipPlan.load(path)

    f_analytic = jax.jit(
        dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig())
    )
    f_plan = jax.jit(
        dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig(plan=plan))
    )
    _, g1, _ = f_analytic(params, batch)
    _, g2, _ = f_plan(params, batch)
    assert max_tree_diff(g1, g2) < 1e-5


def test_measure_tap_conv_times_real_bk_kernels():
    """Conv taps must time the kernels the engine actually runs: the psg bank
    goes through the conv op's vjp on raw activations (no im2col)."""
    from repro.core.taps import Ctx
    from repro.nn.conv import Conv2d, global_avg_pool

    conv = Conv2d("c", 3, 8, (3, 3), strides=(2, 2), padding="SAME")
    head = Dense("head", 8, 5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"c": conv.init(k1), "head": head.init(k2)}

    def loss(params, batch, ctx):
        h = conv(params["c"], batch["image"], ctx.scope("c"))
        h = global_avg_pool(h)
        out = head(params["head"], h[:, None, :], ctx.scope("head"))[:, 0]
        return jnp.sum(out * out, axis=-1)

    batch = {"image": jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))}
    metas = discover_meta(loss, params, batch)
    (conv_meta,) = [m for m in metas.values() if m.conv is not None]
    assert conv_meta.a_shape == (2, 8, 8, 3)
    from repro.tuner.measure import measure_tap

    t = measure_tap(conv_meta, MeasureConfig(repeats=1, warmup=1, max_rows=2))
    for v in (t.ghost_us, t.instantiate_us, t.bk_ghost_us,
              t.bk_instantiate_us, t.second_bwd_us):
        assert v > 0.0


def test_remeasure_at_physical_batch_closes_the_loop():
    """ROADMAP loop: after max_batch settles, branch timings are re-taken at
    the tuned physical batch and only then does the plan finalize."""
    model, params, batch = _two_layer_setup()
    metas = discover_meta(model.loss_with_ctx, params, batch)
    cfg = MeasureConfig(repeats=1, warmup=1, max_rows=2)
    plan = build_plan(metas, measure=cfg, arch="twolayer")
    assert not plan.measured_at_physical
    plan2 = remeasure_at_batch(plan, metas, 8, cfg)
    assert plan2.measured_at_physical
    # batch-free identity: the refreshed plan stays valid for the model
    assert plan2.fingerprint == plan.fingerprint
    assert plan2.matches(metas)
    assert set(dict(plan2.branches)) == set(dict(plan.branches))
    assert set(dict(plan2.bk_branches)) == set(dict(plan.bk_branches))


def test_engine_tune_remeasures_at_tuned_batch(tmp_path, monkeypatch):
    from repro.core.engine import PrivacyEngine

    monkeypatch.setenv("REPRO_TUNER_CACHE", str(tmp_path))
    model, params, batch = _two_layer_setup()
    eng = PrivacyEngine(
        loss_with_ctx=model.loss_with_ctx, batch_size=4, sample_size=1000,
        steps=10, max_grad_norm=1.0, noise_multiplier=1.0,
    )
    plan = eng.tune(params, batch, arch="twolayer", plan_path=None,
                    use_cache=False, measure=MeasureConfig(repeats=1, warmup=1),
                    budget_bytes=1 << 30, hi_cap=16)
    assert plan.physical_batch == 16
    assert plan.measured_at_physical


def test_engine_tune_cache_hit(tmp_path, monkeypatch):
    """A second tune() for the same (arch, device, shapes) skips profiling."""
    from repro.core.engine import PrivacyEngine

    monkeypatch.setenv("REPRO_TUNER_CACHE", str(tmp_path))
    model, params, batch = _two_layer_setup()
    eng = PrivacyEngine(
        loss_with_ctx=model.loss_with_ctx, batch_size=4, sample_size=1000,
        steps=10, max_grad_norm=1.0, noise_multiplier=1.0,
    )
    p1 = eng.tune(params, batch, arch="twolayer", search_max_batch=False,
                  measure=MeasureConfig(repeats=1, warmup=1))
    p2 = eng.tune(params, batch, arch="twolayer", search_max_batch=False,
                  measure=MeasureConfig(repeats=1, warmup=1))
    assert p1 == p2  # identical object state: timings were not re-measured
    assert eng.plan == p1
    # use_cache=False forces a re-measure (timings will differ)
    p3 = eng.tune(params, batch, arch="twolayer", search_max_batch=False,
                  measure=MeasureConfig(repeats=1, warmup=1), use_cache=False,
                  plan_path=None)
    assert p3.fingerprint == p1.fingerprint


def test_noise_finalize_non_private_matches_train_step():
    """Accumulation finalize must not noise/rescale non_private runs."""
    from repro.launch.steps import DPTrainConfig, make_noise_finalize
    from repro.optim import adam, warmup_cosine

    model, params, batch = _two_layer_setup()
    opt = adam()
    dp = DPTrainConfig(clipping_mode="non_private", noise_multiplier=123.0,
                       logical_batch=4)
    fin = make_noise_finalize(opt, warmup_cosine(1e-3, 1, 10), dp)
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32), "rng": jax.random.PRNGKey(0)}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    out1 = fin(dict(state), grads)
    out2 = fin(dict(state), grads)
    # no Gaussian noise: identical grads give identical (deterministic) updates
    assert max_tree_diff(out1["params"], out2["params"]) == 0.0


def test_max_batch_by_memory_monotone_model():
    model, params, batch = _two_layer_setup()
    grad_fn = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig())
    # generous budget: search caps out at hi_cap
    assert max_batch_by_memory(
        grad_fn, params, batch, budget_bytes=1 << 34, hi_cap=8
    ) == 8
    # zero budget: nothing fits
    assert max_batch_by_memory(
        grad_fn, params, batch, budget_bytes=0, hi_cap=8
    ) == 0


# ------------------------------------------------- trial-based max batch --
def test_max_batch_trial_survives_simulated_oom():
    """The retry ladder reports 'does not fit' and keeps the process alive."""
    from repro.tuner import max_batch_by_trial

    model, params, batch = _two_layer_setup()
    grad_fn = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig())
    calls = []

    def runner(b):
        calls.append(b)
        if b > 6:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to allocate"
            )

    got = max_batch_by_trial(
        grad_fn, params, batch, budget_bytes=None, hi_cap=64, runner=runner
    )
    assert got == 6
    # every failing size was retried once (ladder) before being ruled out
    assert calls.count(8) == 2 and calls.count(7) == 2
    # non-OOM failures must NOT be swallowed as "does not fit"
    def broken(b):
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        max_batch_by_trial(
            grad_fn, params, batch, budget_bytes=None, hi_cap=4, runner=broken
        )


def test_max_batch_trial_retries_transient_oom():
    """One flaky OOM (fragmentation) recovers; only a repeat rules a size out."""
    from repro.tuner.max_batch import trial_survives

    failed_once = set()

    def flaky(b):
        if b not in failed_once:
            failed_once.add(b)
            raise RuntimeError("RESOURCE_EXHAUSTED")

    assert trial_survives(flaky, 8, attempts=2)

    def always(b):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    assert not trial_survives(always, 8, attempts=2)


def test_max_batch_trial_converges_to_memory_model():
    """When both drivers apply (CPU executions always fit; the budget binds
    through the pre-filter), the trial search lands on the memory answer."""
    from repro.tuner import max_batch_by_trial

    model, params, batch = _two_layer_setup()
    grad_fn = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig())
    for budget in (1 << 34, 1 << 22):
        by_mem = max_batch_by_memory(
            grad_fn, params, batch, budget_bytes=budget, hi_cap=8
        )
        by_trial = max_batch_by_trial(
            grad_fn, params, batch, budget_bytes=budget, hi_cap=8
        )
        assert by_trial == by_mem


def test_certify_max_batch_method_selection(monkeypatch):
    """Concrete arrays certify by execution; specs fall back to the model."""
    from repro.tuner import certify_max_batch

    model, params, batch = _two_layer_setup()
    grad_fn = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig())
    b, method = certify_max_batch(
        grad_fn, params, batch, budget_bytes=1 << 34, hi_cap=8
    )
    assert (b, method) == (8, "trial")

    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, batch)
    )
    b2, method2 = certify_max_batch(
        grad_fn, specs[0], specs[1], budget_bytes=1 << 34, hi_cap=8
    )
    assert (b2, method2) == (8, "memory")
    # explicit trial on specs is a hard error, not a silent fallback
    with pytest.raises(ValueError):
        certify_max_batch(
            grad_fn, specs[0], specs[1], budget_bytes=1 << 34, hi_cap=8,
            method="trial",
        )
    # env override forces the model even with concrete arrays
    monkeypatch.setenv("REPRO_MAX_BATCH_METHOD", "memory")
    _, method3 = certify_max_batch(
        grad_fn, params, batch, budget_bytes=1 << 34, hi_cap=8
    )
    assert method3 == "memory"


def test_remeasure_at_batch_reraces_stale_kernel_winners():
    """Plan staleness: kernel winners recorded at the probe batch are NOT
    carried into the certified-batch plan — remeasure re-races them there."""
    from repro.tuner.plan import KERNEL_IMPLS

    model, params, batch = _two_layer_setup()
    metas = discover_meta(model.loss_with_ctx, params, batch)
    cfg = MeasureConfig(repeats=1, warmup=1, max_rows=2)
    plan = build_plan(metas, measure=cfg, arch="twolayer")
    assert plan.kernels  # v5 plans always record the raced winners
    # poison the recorded winners with an impl the race could never pick
    # here (pallas is TPU-only; this host races xla alone)
    stale = dataclasses.replace(
        plan,
        kernels=tuple((n, op, "pallas") for n, op, _ in plan.kernels),
    )
    fresh = remeasure_at_batch(stale, metas, 8, cfg)
    assert fresh.measured_at_physical
    # same taps/ops covered, every winner re-raced to a locally valid impl
    assert {(n, op) for n, op, _ in fresh.kernels} == {
        (n, op) for n, op, _ in plan.kernels
    }
    assert all(impl in KERNEL_IMPLS for _, _, impl in fresh.kernels)
    assert all(impl != "pallas" for _, _, impl in fresh.kernels)


def test_accum_microsteps_match_full_train_step():
    """Donated-accumulator path == one train_step on the full logical batch.

    Two microbatches of 2 folded through make_accum_microstep (scattered
    norms, summed grads) and finalized must reproduce the single-shot
    make_train_step update: same rng split discipline -> identical noise,
    per-sample clipping -> grad sums equal, metrics (loss, clip_frac)
    equal.  This is the correctness half of the donation/overlap change.
    """
    from repro.launch.steps import (
        DPTrainConfig,
        make_accum_finalize,
        make_accum_init,
        make_accum_microstep,
        make_clipped_microstep,
        make_train_step,
    )
    from repro.optim import adam, warmup_cosine
    from repro.policies.fixed import FixedPolicy

    model, params, batch = _two_layer_setup()  # logical batch of 4
    opt = adam()
    sched = warmup_cosine(1e-3, 1, 10)
    dp = DPTrainConfig(clipping_mode="mixed_ghost", clip_norm=1.0,
                       noise_multiplier=0.7, logical_batch=4,
                       accumulation_steps=2)
    policy = FixedPolicy(clip_norm=1.0, clip_fn="abadi")
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32), "rng": jax.random.PRNGKey(7),
             "policy": policy.init_state()}

    full_state, full_metrics = make_train_step(model, opt, sched, dp)(
        dict(state), batch
    )

    half = jax.tree_util.tree_map(lambda x: x[:2], batch)
    g_spec = jax.eval_shape(
        make_clipped_microstep(model, dp), params, half, state["policy"]
    )[1]
    acc = make_accum_init(g_spec, 4)()
    micro = make_accum_microstep(model, dp)
    for i in range(2):
        sub = jax.tree_util.tree_map(lambda x: x[i * 2:(i + 1) * 2], batch)
        acc = micro(state["params"], state["policy"], acc, sub,
                    jnp.asarray(i, jnp.int32))
    acc_state, acc_metrics = make_accum_finalize(opt, sched, dp)(
        dict(state), acc
    )

    assert max_tree_diff(acc_state["params"], full_state["params"]) < 1e-5
    assert max_tree_diff(acc_state["opt"], full_state["opt"]) < 1e-5
    assert abs(float(acc_metrics["loss"]) - float(full_metrics["loss"])) < 1e-5
    assert abs(float(acc_metrics["clip_frac"])
               - float(full_metrics["clip_frac"])) < 1e-6
