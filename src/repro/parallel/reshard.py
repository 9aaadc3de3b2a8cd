"""Explicit FSDP weight gathering (the ZeRO-3 compute pattern).

Parameters are stored sharded over the fsdp axes (data[, pod]) — that's the
optimizer-state win — but COMPUTE must see them gathered, with activations
staying batch-sharded.  Left to itself, GSPMD sometimes prefers the dual
plan: keep the weight sharded, replicate the *batch*, and all-reduce the
activations — catastrophically worse (measured: 59 GB of f32[256,4096,*]
all-reduces per layer on yi-6b before this fix; see EXPERIMENTS.md §Perf).

``reshard_param(w, axes)`` pins the intended plan: a sharding constraint that
drops the fsdp axes (=> one all-gather of the bf16 weight per use, freed
after the layer) and keeps the tensor-parallel axes.  In the backward pass
the transpose turns into a reduce-scatter of the weight gradient — exactly
FSDP semantics.  Callers cast to the compute dtype FIRST so the gather moves
bf16, not fp32.

Activated via ``use_reshard_rules(mesh, cfg)`` around tracing/lowering; a
no-op otherwise (single-host smoke tests never notice).

The same context tells ``split_over_samples`` how to run a Pallas kernel
under the mesh: GSPMD cannot partition a Mosaic kernel, so each device runs
it on its own samples inside a ``shard_map``.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import axis_size, logical_rules, mesh_axes

_STATE: contextvars.ContextVar[Optional[tuple]] = contextvars.ContextVar(
    "reshard_rules", default=None
)


@contextlib.contextmanager
def use_reshard_rules(mesh: Mesh, cfg=None):
    rules = logical_rules(mesh, cfg)
    fsdp = set(mesh_axes(mesh)["fsdp"])
    batch = mesh_axes(mesh, cfg)["batch"]
    token = _STATE.set((mesh, rules, fsdp, batch))
    try:
        yield
    finally:
        _STATE.reset(token)


def reshard_param(w: jax.Array, axes: tuple) -> jax.Array:
    """Constrain a parameter to its compute sharding (fsdp axes gathered)."""
    state = _STATE.get()
    if state is None:
        return w
    mesh, rules, fsdp, _ = state
    entries = []
    for dim, logical in zip(w.shape, axes):
        target = tuple(a for a in rules.get(logical, ()) if a not in fsdp)
        if target and dim % axis_size(mesh, target) == 0:
            entries.append(target if len(target) > 1 else target[0])
        else:
            entries.append(None)
    return jax.lax.with_sharding_constraint(
        w, NamedSharding(mesh, P(*entries))
    )


def shard_seq(x: jax.Array) -> jax.Array:
    """Sequence-parallel sharding constraint for a (B, T, d) activation.

    Applied to the layer-scan carry: the activation-checkpoint residuals
    (the dominant train-memory term on TP models — 86 GB on qwen2-72b) are
    then stored sharded T/model_size per device; GSPMD re-gathers the
    sequence just-in-time inside each layer (Korthikanti et al. 2022).
    No-op for dp_only models (model axis already carries batch) and when T
    does not divide.
    """
    state = _STATE.get()
    if state is None or x.ndim != 3:
        return x
    mesh, rules, fsdp, _ = state
    model = tuple(a for a in rules.get("mlp", ()) if a == "model")
    if not model or x.shape[1] % axis_size(mesh, model) != 0:
        return x
    batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = [None, "model", None]
    if batch_ax and x.shape[0] % axis_size(mesh, batch_ax) == 0:
        spec[0] = batch_ax if len(batch_ax) > 1 else batch_ax[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def shard_heads(x: jax.Array, axis: int = 2) -> jax.Array:
    """Constrain a (B, T, H, d) tensor's head dim onto the model axis.

    The SSM path builds q/k by broadcasting shared (B, T, d_state) streams
    over heads — replicated — while v comes from a TP-sharded projection;
    GSPMD then reshards back and forth every chunk (jamba: 727 all-gathers +
    210 permutes per layer-pass). Pinning heads onto the model axis keeps
    the whole scan local.
    """
    state = _STATE.get()
    if state is None or x.ndim <= axis:
        return x
    mesh, rules, fsdp, _ = state
    model = tuple(a for a in rules.get("heads", ()) if a == "model")
    if not model or x.shape[axis] % axis_size(mesh, model) != 0:
        return x
    batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = [None] * x.ndim
    spec[axis] = "model"
    if batch_ax and x.shape[0] % axis_size(mesh, batch_ax) == 0:
        spec[0] = batch_ax if len(batch_ax) > 1 else batch_ax[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def split_over_samples(
    kernel: Callable, *args: jax.Array, dim: int = 0, reduce: bool = False
) -> jax.Array:
    """Run a Pallas kernel on each device's share of the samples.

    ``dim`` of every operand holds the samples (or rows ordered by sample).
    Under the active rules the kernel runs in a ``shard_map`` that splits
    that dim over the batch axes (the longest prefix that divides it; none
    means every device computes the whole): a per-sample result comes back
    split the same way, and a sum over samples (``reduce=True``) adds the
    devices' partial sums with one ``psum``.  Outside the rules, or on one
    device, this is ``kernel(*args)``.
    """
    state = _STATE.get()
    if state is None or state[0].size == 1:
        return kernel(*args)
    mesh, _, _, axes = state
    n = args[0].shape[dim]
    while axes and n % axis_size(mesh, axes) != 0:
        axes = axes[:-1]
    ax = (axes if len(axes) > 1 else axes[0]) if axes else None
    split = P(*([None] * dim), ax)
    if reduce:
        fn = (lambda *a: jax.lax.psum(kernel(*a), axes)) if axes else kernel
        out_spec = P()
    else:
        fn, out_spec = kernel, split
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(split,) * len(args), out_specs=out_spec,
        check_vma=False,
    )(*args)
