"""Stage times from the program's named scopes (``chipbench/scopes.py``): on
hand-made traces with nested ops and instruction names shared by two
programs, on the small TPU trace's own op names, and on a function compiled
here, through the metric readers; a program without the scopes reads
nothing."""
from __future__ import annotations

import contextlib
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench, scopes
from chipbench import trace as T

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
STAGE_METRICS = ("clip.norm_pass_ms", "clip.tap_norms_ms", "clip.second_pass_ms",
                 "loop.noise_update_ms")


def hlo_line(name: str, head: str, op_name: str) -> str:
    return f'  %{name} = {head}(%p), metadata={{op_name="{op_name}"}}'


def ev(name: str, head: str, start: float, end: float) -> T.Event:
    return T.Event(f"%{name} = {head}(f32[8]{{0}} %p)", start, end)


def trace_of(events, steps: int = 1, hi: float = 200) -> T.Trace:
    spans = [T.Event("chipbench.dispatch", 0, 1)] + [
        T.Event("chipbench.sync", 1 + k, hi) for k in range(steps)]
    return T.Trace(ops={"/device:TPU:0": list(events)}, spans=spans)


def test_stage_of_takes_the_first_dp_component():
    fused_probe = "jit(f)/dp.norm_pass/transpose(dp.norm_pass)/jvp(dp.tap_norm)/w/dot"
    assert scopes.stage_of(fused_probe) == "dp.norm_pass"
    assert scopes.stage_of("jit(f)/mul;jit(f)/dp.update/sqrt") == "dp.update"
    assert scopes.stage_of("jit(f)/transpose(jvp())/dot") is None


def test_nested_while_is_counted_once():
    text = "\n".join([
        hlo_line("while.1", "(s32[], f32[8]{0}) while", "jit(f)/dp.norm_pass/while"),
        hlo_line("fusion.2", "f32[8]{0} fusion", "jit(f)/dp.norm_pass/while/body/mul"),
        hlo_line("pad.3", "f32[8]{0} pad", "jit(f)/dp.norm_pass/jvp(dp.tap_norm)/w/pad"),
        hlo_line("conv.4", "f32[8]{0} convolution",
                 "jit(f)/dp.second_pass/transpose(jvp())/conv"),
        hlo_line("fusion.5", "f32[8]{0} fusion", "jit(f)/dp.update/sqrt"),
        hlo_line("fusion.6", "f32[8]{0} fusion", "jit(f)/dp.noise/add"),
        hlo_line("copy.7", "f32[8]{0} copy", "jit(f)/reduce_sum"),
    ])
    tr = trace_of([
        ev("while.1", "(s32[], f32[8]{0}) while", 10, 110),
        ev("fusion.2", "f32[8]{0} fusion", 20, 50),
        ev("pad.3", "f32[8]{0} pad", 60, 100),  # a tap's norm inside the loop
        ev("conv.4", "f32[8]{0} convolution", 120, 160),
        ev("fusion.6", "f32[8]{0} fusion", 160, 165),
        ev("fusion.5", "f32[8]{0} fusion", 165, 170),
        ev("copy.7", "f32[8]{0} copy", 170, 175),
        ev("rng.9", "u32[8]{0} rng-bit-generator", 180, 190),  # another program's op
        ev("copy.7", "f32[4]{0} copy", 190, 195),  # and one under a name this one has
    ], steps=2)
    t = scopes.stage_times(tr, {"_step": text})
    ns = pytest.approx
    assert t.stages["dp.norm_pass"] == ns(100e-9 / 2)
    assert t.tap_norm == ns(40e-9 / 2)
    assert t.stages["dp.second_pass"] == ns(40e-9 / 2)
    assert t.noise_update == ns(10e-9 / 2)
    assert t.unscoped == ns(5e-9 / 2)
    assert t.programs == ns(155e-9 / 2)
    assert (t.stages["dp.norm_pass"] + t.stages["dp.second_pass"] + t.noise_update
            + t.unscoped) == ns(t.programs)


def test_compiler_made_ops_take_their_users_stage_and_the_innermost_op_owns_time():
    text = "\n".join([
        "  %copy-start.1 = (f32[8]{0}, u32[]) copy-start(%p.0)",
        "  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)",
        "  %fusion.2 = f32[8]{0} fusion(%copy-done.1), "
        'metadata={op_name="jit(f)/dp.second_pass/mul"}',
        hlo_line("while.3", "(s32[], f32[8]{0}) while", "jit(f)/dp.second_pass/while"),
        hlo_line("add.4", "f32[8]{0} add", "jit(f)/add"),
    ])
    tr = trace_of([
        ev("copy-start.1", "(f32[8]{0}, u32[]) copy-start", 0, 2),
        ev("copy-done.1", "f32[8]{0} copy-done", 2, 4),
        ev("fusion.2", "f32[8]{0} fusion", 4, 10),
        ev("while.3", "(s32[], f32[8]{0}) while", 10, 50),
        ev("add.4", "f32[8]{0} add", 20, 30),  # an unscoped op inside the loop
    ], hi=60)
    t = scopes.stage_times(tr, {"_step": text})
    assert t.stages["dp.second_pass"] == pytest.approx(40e-9)
    assert t.unscoped == pytest.approx(10e-9)
    assert t.programs == pytest.approx(50e-9)


def test_names_shared_by_two_programs_go_by_shape_then_by_neighbour():
    grad = "\n".join([
        hlo_line("conv.1", "f32[8]{0} convolution", "jit(g)/dp.second_pass/conv"),
        hlo_line("fusion.1", "f32[4]{0} fusion", "jit(g)/dp.norm_pass/mul"),
        hlo_line("copy.2", "f32[8]{0} copy", "jit(g)/dp.norm_pass/copy"),
    ])
    update = "\n".join([
        hlo_line("sqrt.1", "f32[8]{0} sqrt", "jit(u)/dp.update/sqrt"),
        hlo_line("fusion.1", "f32[8]{0} fusion", "jit(u)/dp.update/add"),
        hlo_line("copy.2", "f32[8]{0} copy", "jit(u)/transpose"),
    ])
    tr = trace_of([
        ev("conv.1", "f32[8]{0} convolution", 0, 10),
        ev("copy.2", "f32[8]{0} copy", 10, 20),  # both fit: the grad program's
        ev("fusion.1", "f32[4]{0} fusion", 20, 30),  # the shape says grad
        ev("copy.2", "f32[8]{0} copy", 40, 45),  # both fit: the update's
        ev("sqrt.1", "f32[8]{0} sqrt", 45, 50),
        ev("fusion.1", "f32[8]{0} fusion", 50, 60),  # the shape says update
    ])
    t = scopes.stage_times(tr, {"_grad": grad, "_update": update})
    assert t.stages["dp.second_pass"] == pytest.approx(10e-9)
    assert t.stages["dp.norm_pass"] == pytest.approx(20e-9)
    assert t.stages["dp.update"] == pytest.approx(15e-9)
    assert t.unscoped == pytest.approx(5e-9)


def test_small_tpu_trace_by_its_own_op_names():
    """The op events of a trace recorded on a v5e, against a program text
    that scopes the pads and the kernel as one tap's norm work."""
    small = T.load(str(SMALL))
    tap = {"pad.12", "pad.13", "ghost_norm_sq_pallas.1"}
    lines, seen = [], set()
    for e in small.ops["/device:TPU:0"]:
        name, _, rest = e.name.partition(" = ")
        name = name.lstrip("%")
        if name in seen:
            continue
        seen.add(name)
        op = "jit(step)/dp.norm_pass/dp.tap_norm/w/pad" if name in tap else "jit(step)/add"
        lines.append(hlo_line(name, scopes.head(rest), op))
    t = scopes.stage_times(small, {"_step": "\n".join(lines)})
    lo, hi = small.window
    kernel_and_pads = T.union([(e.start, e.end) for e in small.ops["/device:TPU:0"]
                               if e.name.split(" = ")[0].lstrip("%") in tap])
    want = sum(e - s for s, e in T.clip(kernel_and_pads, lo, hi)) / 1e9 / 3
    assert t.tap_norm == pytest.approx(want) and t.tap_norm > 0
    assert t.stages["dp.norm_pass"] == pytest.approx(want)
    assert t.programs == pytest.approx(small.busy_s() / 3)
    # without a program that fits, nothing is attributed
    assert scopes.stage_times(small, {}).programs == 0.0


def _compiled_ctx(scoped: bool):
    def step(x, w):
        ctx = jax.named_scope if scoped else (lambda name: contextlib.nullcontext())
        with ctx("dp.norm_pass"):
            h = jnp.tanh(x @ w)
            with ctx("dp.tap_norm"), ctx("w"):
                n = jnp.sum(h * h, axis=1)
        with ctx("dp.second_pass"):
            g = x.T @ (h * n[:, None])
        with ctx("dp.update"):
            return w - 0.1 * jnp.sign(g)

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    compiled = jax.jit(step).lower(x, w).compile()
    trainer = types.SimpleNamespace(_step=compiled, _batch=jax.jit(jnp.sin))
    events, at = [], 0.0
    for name, rest in scopes.INSTRUCTION.findall(compiled.as_text()):
        if " parameter(" in f" {rest}" or "op_name" not in rest:
            continue
        events.append(T.Event(f"%{name} = {rest.split(', metadata=')[0]}", at, at + 10))
        at += 10
    return types.SimpleNamespace(trace=trace_of(events, hi=at + 1), trainer=trainer), events


def test_metric_readers_on_a_compiled_function():
    ctx, events = _compiled_ctx(scoped=True)
    values = {m: bench.metric_module(m).read(ctx) for m in STAGE_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    times = scopes.read(ctx)
    assert times.programs == pytest.approx(len(events) * 10e-9)
    covered = times.stages["dp.norm_pass"] + times.stages["dp.second_pass"] \
        + times.noise_update + times.unscoped
    assert covered == pytest.approx(times.programs)
    assert values["clip.tap_norms_ms"] <= values["clip.norm_pass_ms"]


def test_a_program_without_scopes_reads_nothing():
    ctx, _ = _compiled_ctx(scoped=False)
    assert all(bench.metric_module(m).read(ctx) is None for m in STAGE_METRICS)
    # a trace with no device plane (a CPU run) reads nothing either
    ctx.trace = T.Trace(ops={}, spans=ctx.trace.spans)
    assert all(bench.metric_module(m).read(ctx) is None for m in STAGE_METRICS)
