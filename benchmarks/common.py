"""Shared benchmark machinery: timing, memory-model probes, tiny models."""
from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.clipping import MODES, ClipConfig, dp_value_and_clipped_grad
from repro.core.taps import Ctx
from repro.data.synthetic import synthetic_vision_batch
from repro.models.cnn import VGG
from repro.models.losses import per_sample_xent
from repro.nn.conv import Conv2d, global_avg_pool
from repro.nn.module import Dense, GroupNorm


def time_fn(fn: Callable, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall seconds per call (jit-compiled fns; blocks on output)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def compiled_memory_bytes(fn: Callable, *specs) -> int:
    """Peak-memory model from AOT compile: args + outputs + temps."""
    compiled = jax.jit(fn).lower(*specs).compile()
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


class SmallCNN:
    """The paper's CIFAR CNN analogue (Table 4 row 1, ~0.5M params)."""

    def __init__(self, n_classes: int = 10, width: int = 32):
        w = width
        self.c1 = Conv2d("c1", 3, w, (3, 3))
        self.g1 = GroupNorm("g1", w, groups=8)
        self.c2 = Conv2d("c2", w, 2 * w, (3, 3), strides=(2, 2))
        self.g2 = GroupNorm("g2", 2 * w, groups=8)
        self.c3 = Conv2d("c3", 2 * w, 2 * w, (3, 3), strides=(2, 2))
        self.head = Dense("head", 2 * w, n_classes)

    def init(self, key):
        ks = jax.random.split(key, 6)
        return {
            "c1": self.c1.init(ks[0]), "g1": self.g1.init(ks[1]),
            "c2": self.c2.init(ks[2]), "g2": self.g2.init(ks[3]),
            "c3": self.c3.init(ks[4]), "head": self.head.init(ks[5]),
        }

    def loss_with_ctx(self, params, batch, ctx: Ctx):
        h = jax.nn.relu(self.g1(params["g1"],
                                self.c1(params["c1"], batch["image"], ctx.scope("c1")),
                                ctx.scope("g1")))
        h = jax.nn.relu(self.g2(params["g2"],
                                self.c2(params["c2"], h, ctx.scope("c2")),
                                ctx.scope("g2")))
        h = self.c3(params["c3"], h, ctx.scope("c3"))
        h = global_avg_pool(h)
        logits = self.head(params["head"], h[:, None, :], ctx.scope("head"))[:, 0]
        return per_sample_xent(logits[:, None, :], batch["label"][:, None],
                               batch.get("mask"))


def cnn_batch(batch: int, image: int = 32, step: int = 0):
    return synthetic_vision_batch(
        batch=batch, image=image, channels=3, n_classes=10, step=step
    )


def clipping_step_fn(model, mode: str, clip_norm: float = 1.0):
    return jax.jit(
        dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig(mode=mode, clip_norm=clip_norm))
    )


MODES_BENCH = list(MODES)
