"""Paper Table 4/6: per-step time and memory of each clipping algorithm at a
fixed physical batch size (CNN on 32x32 images, the paper's CIFAR setting).

Memory is the XLA compiled-program peak model (args+outputs+temps) — the CPU
analogue of the paper's `torch.cuda` active memory.
"""
from __future__ import annotations

import jax

from benchmarks.common import (
    MODES_BENCH,
    SmallCNN,
    clipping_step_fn,
    cnn_batch,
    compiled_memory_bytes,
    time_fn,
)


def run(batch: int = 64, image: int = 32) -> list[tuple[str, float, str]]:
    rows = vgg11_memory()
    model = SmallCNN()
    params = model.init(jax.random.PRNGKey(0))
    batch_data = cnn_batch(batch, image)
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, batch_data)
    )
    for mode in MODES_BENCH:
        from repro.core.clipping import ClipConfig, dp_value_and_clipped_grad

        raw_fn = dp_value_and_clipped_grad(
            model.loss_with_ctx, ClipConfig(mode=mode, clip_norm=1.0)
        )
        step = jax.jit(raw_fn)
        t = time_fn(step, params, batch_data)
        mem = compiled_memory_bytes(raw_fn, *specs)
        rows.append(
            (f"table4_cnn_b{batch}_{mode}", t * 1e6, f"mem_mb={mem / 1e6:.1f}")
        )
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))


def vgg11_memory(batch: int = 128) -> list[tuple[str, float, str]]:
    """Paper Table 6 setting: VGG-11 on 32x32, physical batch 128.

    Paper (GB): Opacus 6.19, Ghost 1.85, Mixed 1.85, NonDP 1.83.
    Memory-model analogue (no timing — VGG11 x 6 modes is compile-only).
    """
    from repro.models.cnn import VGG

    model = VGG("vgg11", n_classes=10)
    params = model.init(jax.random.PRNGKey(0))
    bd = cnn_batch(batch, 32)
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, bd)
    )
    rows = []
    from repro.core.clipping import ClipConfig, dp_value_and_clipped_grad

    for mode in MODES_BENCH:
        fn = dp_value_and_clipped_grad(model.loss_with_ctx, ClipConfig(mode=mode))
        mem = compiled_memory_bytes(fn, *specs)
        rows.append((f"table6_vgg11_b{batch}_{mode}", 0.0, f"mem_gb={mem/1e9:.2f}"))
    return rows
