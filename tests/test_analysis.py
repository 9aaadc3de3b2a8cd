"""repro.analysis: taint/coverage audits on adversarial fixtures + registry sweep.

The fixtures are deliberately tiny hand-rolled ``loss_with_ctx`` models (the
same contract the clipping engines consume) with one planted defect each:
an injected batch-norm (cross-sample stats), an uncovered param leaf, a
gradient route around a tap, a dead leaf, a declared-but-unthreaded tap.
The sweep tests then assert every *shipped* config audits clean modulo the
documented MoE ``routed_scatter`` allowlist.
"""
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    ALLOWLIST,
    audit_arch,
    audit_loss_fn,
    jaxpr_hygiene,
    donation_lint,
)
from repro.analysis import allowlist as allowlist_mod
from repro.analysis.report import FINDINGS_FILENAME, Finding, write_findings
from repro.core.clipping import discover_meta
from repro.configs.registry import ARCHS
from repro.obs.sinks import read_jsonl

B, D_IN, D_H, D_OUT = 3, 5, 7, 2

MOE_ARCHS = {"mixtral-8x7b", "arctic-480b", "jamba-1.5-large-398b"}


def _params(*, sneaky=False, dead=False):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {
        "lin": {"w": jax.random.normal(k[0], (D_IN, D_H)) * 0.1},
        "out": {"w": jax.random.normal(k[1], (D_H, D_OUT)) * 0.1},
    }
    if sneaky:
        p["sneaky"] = {"w": jax.random.normal(k[2], (D_IN, D_OUT)) * 0.1}
    if dead:
        p["dead"] = {"w": jax.random.normal(k[3], (D_H,)) * 0.1}
    return p


def _batch():
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    return {
        "x": jax.random.normal(kx, (B, D_IN)),
        "y": jax.random.normal(ky, (B, D_OUT)),
    }


def _loss_fn(*, batchnorm=False, sneaky=False, bypass=False):
    """Two tapped matmuls with optional planted defects."""

    def loss(params, batch, ctx):
        x = batch["x"]
        if batchnorm:
            # the BatchNorm failure mode: per-feature stats ACROSS the batch
            x = (x - x.mean(axis=0, keepdims=True)) / jnp.sqrt(
                x.var(axis=0, keepdims=True) + 1e-5
            )
        s = x @ params["lin"]["w"]
        s = ctx.tap(
            "lin", s, kind="matmul", a=x, T=1, D=D_IN, p=D_H, param_path="lin/w"
        )
        h = jax.nn.relu(s)
        if bypass:
            h = h + x @ params["lin"]["w"]  # second use of lin/w, untapped
        o = h @ params["out"]["w"]
        o = ctx.tap(
            "out", o, kind="matmul", a=h, T=1, D=D_H, p=D_OUT, param_path="out/w"
        )
        if sneaky:
            o = o + x @ params["sneaky"]["w"]  # untapped trainable leaf
        return ((o - batch["y"]) ** 2).sum(axis=-1)

    return loss


# -- pass 1: per-sample isolation --------------------------------------------


def test_clean_fixture_audits_clean():
    assert audit_loss_fn(_loss_fn(), _params(), _batch()) == []


def test_injected_batchnorm_caught_with_provenance():
    findings = audit_loss_fn(
        _loss_fn(batchnorm=True), _params(), _batch(), arch="fixture"
    )
    mixed = [f for f in findings if f.code == "sample_mixing"]
    assert mixed, findings
    assert all(f.severity == "error" for f in mixed)
    site = next(f for f in mixed if f.subject == "lin")
    # eqn-level provenance: network input at the root, tap-add site at the tip
    assert site.provenance[0].startswith("batch[x]")
    assert site.provenance[-1].startswith("tap add:")
    assert len(site.provenance) >= 3  # at least one real eqn hop between them


# -- pass 2: gradient-path coverage ------------------------------------------


def test_uncovered_param_named_by_path():
    findings = audit_loss_fn(
        _loss_fn(sneaky=True), _params(sneaky=True), _batch(), arch="fixture"
    )
    assert [
        (f.code, f.severity, f.subject) for f in findings
    ] == [("uncovered_param", "error", "sneaky/w")]


def test_frozen_prefix_waives_uncovered_param():
    findings = audit_loss_fn(
        _loss_fn(sneaky=True),
        _params(sneaky=True),
        _batch(),
        frozen_prefixes=("sneaky",),
    )
    assert findings == []


def test_tap_bypass_detected():
    findings = audit_loss_fn(
        _loss_fn(bypass=True), _params(), _batch(), arch="fixture"
    )
    assert [(f.code, f.severity, f.subject) for f in findings] == [
        ("tap_bypass", "error", "lin")
    ]
    assert "lin/w" in findings[0].detail


def test_dead_param_is_warn_only():
    findings = audit_loss_fn(_loss_fn(), _params(dead=True), _batch())
    assert [(f.code, f.severity, f.subject) for f in findings] == [
        ("dead_param", "warn", "dead/w")
    ]


def test_declared_but_unthreaded_tap_is_error():
    loss, params, batch = _loss_fn(), _params(), _batch()
    meta = dict(discover_meta(loss, params, batch, clip=None))
    meta["ghost"] = meta["lin"]  # declared, never added in the graph
    findings = audit_loss_fn(loss, params, batch, meta=meta)
    assert [(f.code, f.severity, f.subject) for f in findings] == [
        ("tap_unthreaded", "error", "ghost")
    ]


# -- pass 3: tracing hygiene --------------------------------------------------


def test_hygiene_clean_jaxpr():
    closed = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(jnp.ones(3))
    assert jaxpr_hygiene(closed) == []


def test_planted_f64_promotion_detected():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: jnp.sin(x.astype(jnp.float64)))(
            jnp.ones(3, jnp.float32)
        )
    findings = jaxpr_hygiene(closed, arch="fixture")
    assert any(f.code == "f64_promotion" and f.severity == "warn" for f in findings)


def test_host_callback_in_step_detected():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2.0

    closed = jax.make_jaxpr(f)(jnp.ones(3))
    findings = jaxpr_hygiene(closed, arch="fixture")
    assert any(f.code == "host_callback" for f in findings)


def test_donation_lint_fixture_tree(tmp_path):
    launch = tmp_path / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    (launch / "train.py").write_text(
        textwrap.dedent(
            """
            import jax

            jit_step = jax.jit(step_fn).lower(state, batch).compile()
            micro_fn = jax.jit(micro, donate_argnums=(2,)).lower(g, b, acc).compile()
            fin_fn = jax.jit(fin, donate_argnums=(1,)).lower(state).compile()
            """
        )
    )
    findings = donation_lint(repo_root=tmp_path)
    assert all(f.code == "donation_miss" and f.severity == "warn" for f in findings)
    assert sorted(f.subject.rsplit(":", 1)[-1] for f in findings) == [
        "fin_fn",
        "jit_step",
    ]


def test_donation_lint_real_repo_clean():
    assert donation_lint() == []


# -- allowlist + findings plumbing --------------------------------------------


def test_stale_allowlist_entry_warns():
    out, used = allowlist_mod.apply("mixtral-8x7b", [], entries=ALLOWLIST)
    assert used == set()
    assert [(f.code, f.severity) for f in out] == [("stale_allowlist", "warn")]


def test_unknown_finding_code_rejected():
    with pytest.raises(ValueError):
        Finding(code="nope", severity="error", arch="-", subject="s", detail="d")
    with pytest.raises(ValueError):
        Finding(
            code="sample_mixing", severity="fatal", arch="-", subject="s", detail="d"
        )


def test_findings_jsonl_roundtrip(tmp_path):
    findings = [
        Finding(
            code="sample_mixing",
            severity="error",
            arch="fixture",
            subject="lin",
            detail="mixed",
            provenance=("batch[x] (network input)", "tap add: add"),
        ),
        Finding(
            code="f64_promotion",
            severity="warn",
            arch="fixture",
            subject="sin",
            detail="wide",
        ),
    ]
    path = tmp_path / FINDINGS_FILENAME
    write_findings(findings, path)
    recs = read_jsonl(path)
    assert [r["code"] for r in recs] == ["sample_mixing", "f64_promotion"]
    assert recs[0]["kind"] == "finding"
    assert recs[0]["provenance"] == [
        "batch[x] (network input)",
        "tap add: add",
    ]
    assert "provenance" not in recs[1]


# -- registry sweep ------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_registry_config_audits_clean(name):
    findings = audit_arch(name, hygiene_pass=False)
    assert [f for f in findings if f.severity == "error"] == []
    assert [f for f in findings if f.severity == "warn"] == []
    infos = [f for f in findings if f.severity == "info"]
    if name in MOE_ARCHS:
        # the documented waiver must actually be exercised, not silently unused
        assert infos
        assert all(
            f.code == "routed_scatter" and f.allowlisted_by for f in infos
        )
    else:
        assert infos == []


def test_allowlist_off_surfaces_moe_error():
    findings = audit_arch(
        "mixtral-8x7b", hygiene_pass=False, apply_allowlist=False
    )
    errors = [f for f in findings if f.severity == "error"]
    assert errors
    assert all(f.code == "routed_scatter" for f in errors)


def test_step_hygiene_clean_end_to_end():
    # full audit including the jitted-train-step hygiene pass on one config
    assert audit_arch("yi-6b") == []


def test_cli_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main

    assert main(["--arch", "yi-6b", "--no-hygiene"]) == 0
    assert "0 error(s)" in capsys.readouterr().out

    rc = main(
        [
            "--arch",
            "mixtral-8x7b",
            "--no-hygiene",
            "--no-allowlist",
            "--out",
            str(tmp_path),
        ]
    )
    assert rc == 1
    capsys.readouterr()
    recs = read_jsonl(tmp_path / FINDINGS_FILENAME)
    assert any(r["code"] == "routed_scatter" for r in recs)
