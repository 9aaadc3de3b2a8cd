"""PrivacyEngine integration + train-loop fault tolerance."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import build_model, get_arch
from repro.core.engine import PrivacyEngine
from repro.data.synthetic import SyntheticLMConfig, synthetic_lm_batch


def _engine(model, mode="mixed_ghost", **kw):
    defaults = dict(
        loss_with_ctx=model.loss_with_ctx, batch_size=4, sample_size=10_000,
        steps=100, max_grad_norm=0.5, noise_multiplier=1.0, mode=mode,
    )
    defaults.update(kw)
    return PrivacyEngine(**defaults)


def test_engine_sigma_from_epsilon():
    model = build_model(get_arch("yi-6b").reduced())
    e = _engine(model, noise_multiplier=None, target_epsilon=2.0)
    assert e.noise_multiplier > 0.3
    eps, delta = e.privacy_spent(steps=100)
    assert eps <= 2.0 + 1e-6


def test_engine_clip_noise_pipeline():
    cfg = get_arch("yi-6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = _engine(model)
    data = SyntheticLMConfig(vocab=cfg.vocab, seq_len=12, batch=4)
    batch = synthetic_lm_batch(data, 0)
    engine.validate(params, batch)
    loss, gsum, aux = jax.jit(engine.clipped_grad_fn())(params, batch)
    assert jnp.isfinite(loss)
    # per-sample contributions bounded by R
    assert bool(jnp.all(aux["clip_factors"] * aux["per_sample_norms"]
                        <= engine.max_grad_norm * 1.001))
    g1 = engine.privatize(gsum, jax.random.PRNGKey(1))
    g2 = engine.privatize(gsum, jax.random.PRNGKey(2))
    # noise actually applied and key-dependent
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)))
    assert d > 0
    # accounting moves
    engine.record_step(10)
    eps10 = engine.accountant.get_epsilon(engine.target_delta)
    engine.record_step(10)
    assert engine.accountant.get_epsilon(engine.target_delta) > eps10


def test_train_cli_resume_and_fault_injection(tmp_path):
    from repro.launch.train import main

    argv = [
        "--arch", "yi-6b", "--reduced", "--steps", "8", "--batch", "2",
        "--seq", "16", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
        "--fail-at-step", "5", "--auto-restart", "2", "--log-every", "4",
    ]
    assert main(argv) == 0
    from repro.checkpoint import latest_step

    assert latest_step(tmp_path) == 8


def test_train_cli_resume_from_pre_policy_checkpoint(tmp_path):
    """A checkpoint written before the policies subsystem (no state["policy"]
    subtree) must resume: the missing policy state is filled with init."""
    import numpy as np

    from repro.launch.train import main

    base = [
        "--arch", "yi-6b", "--reduced", "--batch", "2", "--seq", "16",
        "--ckpt-dir", str(tmp_path), "--log-every", "2",
        "--clip-policy", "quantile",
    ]
    assert main(base + ["--steps", "2"]) == 0
    # simulate a legacy artifact: strip the policy/* leaves in place
    path = tmp_path / "step_2.npz"
    with np.load(path) as z:
        legacy = {k: z[k] for k in z.files if not k.startswith("policy/")}
    np.savez(path, **legacy)
    assert main(base + ["--steps", "4", "--resume"]) == 0
    with np.load(tmp_path / "step_4.npz") as z:
        # the filled-in policy state adapted over the resumed steps
        assert "policy/clip_norm" in z.files
        assert int(z["policy/step"]) == 2


def test_train_cli_profile_window_marks_steps_and_accounting(tmp_path, capsys):
    """--profile-steps 1:2 traces exactly steps 1 and 2, each under its
    dp.train_step annotation with one dp.accountant span inside, and the
    obs CLI's timeline reads the two steps back."""
    from repro.launch.train import main
    from repro.obs.__main__ import main as obs_cli
    from repro.obs.timeline import annotated_steps, load_trace_events

    argv = [
        "--arch", "yi-6b", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "16", "--obs-dir", str(tmp_path), "--profile-steps", "1:2",
        "--log-every", "4",
    ]
    assert main(argv) == 0
    prof = tmp_path / "profile"
    steps = annotated_steps(prof)
    assert [s["step"] for s in steps] == [1, 2]
    accounting = [e for e in load_trace_events(prof) if e.get("name") == "dp.accountant"]
    assert len(accounting) == 2
    for s, a in zip(steps, sorted(accounting, key=lambda e: e["ts"])):
        assert s["ts_us"] <= a["ts"] and a["ts"] + a["dur"] <= s["ts_us"] + s["dur_us"]
    capsys.readouterr()
    assert obs_cli([str(tmp_path), "--timeline"]) == 0
    assert "profiled steps: 2 dp.train_step annotation(s)" in capsys.readouterr().out


def test_train_cli_poisson(tmp_path):
    from repro.launch.train import main

    argv = [
        "--arch", "xlstm-350m", "--reduced", "--steps", "3", "--batch", "2",
        "--seq", "16", "--poisson", "--log-every", "1",
    ]
    assert main(argv) == 0


def test_train_cli_accumulation_path(tmp_path, monkeypatch):
    """--tune with a hi-cap of 1 forces physical=1, accum=2: the donated-
    accumulator loop must run end-to-end (init/micro/finalize AOT programs,
    one host sync per logical batch) and checkpoint at the requested step."""
    from repro.checkpoint import latest_step
    from repro.launch.train import main

    monkeypatch.setenv("REPRO_TUNER_CACHE", str(tmp_path / "plans"))
    argv = [
        "--arch", "xlstm-350m", "--reduced", "--steps", "2", "--batch", "2",
        "--seq", "16", "--tune", "--tune-hi-cap", "1", "--log-every", "1",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
    ]
    assert main(argv) == 0
    assert latest_step(tmp_path) == 2
