"""Stage scopes in the compiled DP step.

Each op of a compiled step carries its stage in the ``op_name`` of its
metadata: the first path component that starts with ``dp.`` (``dp.norm_pass``,
``dp.second_pass``, ``dp.noise``, ``dp.update``), and ``dp.tap_norm``
followed by the tap for the per-tap norm work inside the norm pass.  A device
trace names each op it ran, so these scopes split a traced step by stage.
The scopes are metadata only: with them patched out the compiled program is
the same once the metadata is stripped.
"""
from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import build_model, get_arch
from repro.core.clipping import discover_meta
from repro.core.decision import decide
from repro.core.engine import PrivacyEngine
from repro.launch.steps import DPTrainConfig, make_train_step
from repro.models.cnn import VGG
from repro.optim import adam, apply_updates
from repro.optim.schedules import constant

from helpers import lm_batch

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*?\s([\w\-]+)\(.*?op_name="([^"]*)"', re.M
)
METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
# the module's tables of source files, functions and stack frames
SOURCE_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)\n", re.M
)
TAP = re.compile(r"dp\.tap_norm\)*/([^/]+)")
B = 2


def stage(op_name: str):
    for name in op_name.split(";"):
        for part in name.split("/"):
            if part.startswith("dp."):
                return part
    return None


def instructions(hlo: str) -> list[tuple[str, str, str]]:
    """(name, opcode, op_name) of the instructions named from the jitted
    function down; reducer regions and parameters carry relative names."""
    return [i for i in INSTRUCTION.findall(hlo) if i[2].startswith("jit(")]


def cnn_step_hlo() -> tuple[str, dict]:
    """A VGG step as the CNN cell runs it: clipped gradient, privatize, Adam."""
    model = VGG("vgg11", n_classes=10, in_ch=3, groups=4)
    engine = PrivacyEngine(
        loss_with_ctx=model.loss_with_ctx, batch_size=B, sample_size=1000, steps=10,
        max_grad_norm=1.0, noise_multiplier=1.0, mode="mixed_ghost",
    )
    opt = adam()
    grad_fn = engine.clipped_grad_fn()

    def step(params, opt_state, batch, key):
        loss, grad_sum, _ = grad_fn(params, batch)
        grads = engine.privatize(grad_sum, key)
        upd, opt_state = opt.update(grads, opt_state, params, jnp.int32(0), 1e-3)
        return apply_updates(params, upd), opt_state, loss

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"image": jax.ShapeDtypeStruct((B, 16, 16, 3), jnp.float32),
             "label": jax.ShapeDtypeStruct((B,), jnp.int32)}
    hlo = jax.jit(step).lower(
        params, jax.eval_shape(opt.init, params), batch, jax.random.PRNGKey(1)
    ).compile().as_text()
    return hlo, discover_meta(model.loss_with_ctx, params, batch)


def lm_step_hlo() -> tuple[str, dict]:
    """The train CLI's step for a reduced xLSTM (scans, late taps, LM head)."""
    model = build_model(get_arch("xlstm-350m").reduced())
    dp = DPTrainConfig(clipping_mode="mixed_ghost", clip_norm=1.0, noise_multiplier=1.0,
                       logical_batch=B, accumulation_steps=1)
    opt = adam()
    step = make_train_step(model, opt, constant(1e-3), dp)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = {"params": params, "opt": jax.eval_shape(opt.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32),
             "rng": jax.eval_shape(lambda: jax.random.PRNGKey(0))}
    batch = jax.eval_shape(lambda: lm_batch(jax.random.PRNGKey(0), B, 16, 128))
    hlo = jax.jit(step).lower(state, batch).compile().as_text()
    return hlo, discover_meta(model.loss_with_ctx, params, batch)


STEPS = {"cnn": cnn_step_hlo, "lm": lm_step_hlo}


@pytest.fixture(scope="module", params=sorted(STEPS))
def compiled(request):
    return request.param, *STEPS[request.param]()


def test_every_backward_op_is_in_a_pass_and_the_second_pull_in_the_second(compiled):
    _, hlo, _ = compiled
    ops = instructions(hlo)
    backward = [(opc, op) for _, opc, op in ops if "transpose(" in op]
    assert backward
    assert {stage(op) for _, op in backward} <= {"dp.norm_pass", "dp.second_pass"}
    second = [(opc, op) for opc, op in backward if stage(op) == "dp.second_pass"]
    # the weight gradients of the second pull are contractions
    assert any(opc in ("convolution", "dot") for opc, _ in second), second[:5]
    assert not any(TAP.search(op) for _, op in second)


def test_tap_norms_carry_their_tap_under_the_norm_pass(compiled):
    kind, hlo, meta = compiled
    by_tap: dict[str, set] = {}
    for _, opc, op in instructions(hlo):
        m = TAP.search(op)
        if m:
            assert stage(op) == "dp.norm_pass", op
            by_tap.setdefault(m.group(1), set()).add(opc)
    branches = {
        m.param_path.replace("/", "."): decide(m, mode="mixed_ghost")
        for m in meta.values() if m.kind == "matmul"
    }
    assert set(branches) <= set(by_tap), sorted(set(branches) - set(by_tap))
    if kind == "cnn":  # ghost norms and instantiated norms alike
        assert set(branches.values()) == {"ghost", "instantiate"}


def test_noise_and_the_update_have_their_scopes(compiled):
    _, hlo, _ = compiled
    stages = {stage(op) for _, _, op in instructions(hlo)}
    assert {"dp.norm_pass", "dp.second_pass", "dp.noise", "dp.update"} <= stages


def test_scopes_change_nothing_but_metadata(compiled, monkeypatch):
    kind, scoped, _ = compiled
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = STEPS[kind]()[0]
    assert "dp." in scoped and "dp." not in plain
    def program(hlo):
        return SOURCE_TABLES.sub("", METADATA.sub("", hlo))

    assert program(scoped) == program(plain)
