"""Ahead-of-time compiles of the clipping kernels for a described TPU v5e.

The interpret-mode tests (tests/test_kernels.py) check what the kernels
compute; only the TPU compiler checks their block shapes, layouts and VMEM
use.  Each test here lowers one dispatched op at a real width for one chip of
a described ``v5e:2x2`` topology, with the dispatch steered to the chip, and
asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Nothing runs, so no chip is needed.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.parallel.reshard import use_reshard_rules


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The 2x2 host as the train CLI lays it out: (data=4, model=1)."""
    return Mesh(
        np.array(topo.devices).reshape(4, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


@pytest.fixture
def on_tpu(monkeypatch):
    """Trace as the chip would: Pallas by default, compiled, not interpreted."""
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

# (op, argument shapes and dtypes): each at a width the training path meets
CASES = {
    # VGG19 on CIFAR-10, a 4x4 conv tap (3x3x512 -> 512) at batch 64
    "ghost_norm_vgg19_4x4": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((64, 16, 4608), F32), ((64, 16, 512), F32)],
    ),
    # VGG19 on CIFAR-10, a 2x2 conv tap and the 512 -> 10 head at batch 64
    "ghost_norm_vgg19_2x2": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((64, 4, 4608), F32), ((64, 4, 512), F32)],
    ),
    "ghost_norm_vgg19_head": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((64, 1, 512), F32), ((64, 1, 10), F32)],
    ),
    # VGG19's 4x4 tap with a ragged last step (100 = 6 x 16 + 4 samples)
    "ghost_norm_vgg19_4x4_ragged": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((100, 16, 2304), F32), ((100, 16, 512), F32)],
    ),
    # a ViT-L/16 tap at 224 px (197 tokens, bf16 activations): one sample a step
    "ghost_norm_vit_l16": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((16, 197, 1024), BF16), ((16, 197, 4096), F32)],
    ),
    # xlstm-350m lm_head at seq 4096 (d_model 1024, vocab 50304)
    "ghost_norm_xlstm_lm_head": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((2, 4096, 1024), F32), ((2, 4096, 50304), F32)],
    ),
    # xlstm-350m token embedding at seq 4096
    "embedding_ghost_norm_xlstm": (
        lambda ids, g: dispatch.embedding_ghost_norm_sq(ids, g),
        [((2, 4096), I32), ((2, 4096, 1024), F32)],
    ),
    # VGG19 16x16 conv tap (3x3x128 -> 128), batch 256: the bk_mixed book
    "book_weighted_grad_vgg19_16x16": (
        lambda a, g, w: dispatch.book_weighted_grad(a, g, w),
        [((1, 256 * 256, 1152), F32), ((1, 256 * 256, 128), F32),
         ((1, 256 * 256), F32)],
    ),
    # the same tap banked as per-sample gradients
    "psg_contract_vgg19_16x16": (
        lambda psg, c: dispatch.psg_contract(psg, c),
        [((256, 1152 * 128), F32), ((256,), F32)],
    ),
    "flash_attention_forward": (
        lambda q, k, v: dispatch.flash_attention(q, k, v, causal=True),
        [((1, 1024, 8, 128), BF16)] * 3,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, on_tpu):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case,packed", [
    ("ghost_norm_vgg19_2x2", True),
    ("ghost_norm_vgg19_4x4", True),
    ("ghost_norm_xlstm_lm_head", False),
])
def test_ghost_norm_call_names_its_tiling(case, packed, one_chip, on_tpu):
    """A trace counts the packed path's calls by their name."""
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln and " = " in ln]
    assert len(calls) == 1
    name = calls[0].split(" = ", 1)[0]
    assert "ghost_norm_sq_pallas" in name
    assert ("ghost_norm_sq_pallas_packed" in name) == packed


# the clipping ops at the VGG19 widths above, with the samples split over a
# 4-chip mesh: GSPMD cannot partition a Mosaic kernel, so each must run per
# device (reshard.split_over_samples)
MESH_CASES = {
    "ghost_norm": (
        lambda a, g: dispatch.ghost_norm_sq(a, g),
        [((64, 16, 4608), F32), ((64, 16, 512), F32)], 0,
    ),
    "embedding_ghost_norm": (
        lambda ids, g: dispatch.embedding_ghost_norm_sq(ids, g),
        [((8, 4096), I32), ((8, 4096, 1024), F32)], 0,
    ),
    "book_weighted_grad": (
        lambda a, g, w: dispatch.book_weighted_grad(a, g, w),
        [((1, 256 * 256, 1152), F32), ((1, 256 * 256, 128), F32),
         ((1, 256 * 256), F32)], 1,
    ),
    "psg_contract": (
        lambda psg, c: dispatch.psg_contract(psg, c),
        [((256, 1152 * 128), F32), ((256,), F32)], 0,
    ),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_kernel_compiles_split_over_four_chips(case, four_chips, on_tpu):
    fn, shapes, dim = MESH_CASES[case]
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(
            four_chips, P(*([None] * dim), "data")
        ))
        for s, dt in shapes
    ]
    with use_reshard_rules(four_chips):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_beit_large_clipped_grad_compiles_for_one_chip(one_chip, on_tpu):
    """BEiT-large/16 at 224 px, batch 128, under mixed ghost clipping: the
    whole clipped-gradient program for one v5e, with the ghost kernel in it
    (every matmul tap, T = 197 and 196) and its temporaries under 10 GB
    (5.03 GB when written), so that a memory regression shows without a chip."""
    from repro.configs.paper_native import BEIT_LARGE
    from repro.core.engine import PrivacyEngine
    from repro.models.vit import BEiT

    model = BEiT(BEIT_LARGE, image_size=224, patch=16, n_classes=10)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {"image": jax.ShapeDtypeStruct((128, 224, 224, 3), F32, sharding=one_chip),
             "label": jax.ShapeDtypeStruct((128,), I32, sharding=one_chip),
             "mask": jax.ShapeDtypeStruct((128,), F32, sharding=one_chip)}
    engine = PrivacyEngine(loss_with_ctx=model.loss_with_ctx, batch_size=128, sample_size=50000,
                           steps=2000, max_grad_norm=1.0, noise_multiplier=1.0,
                           mode="mixed_ghost")
    compiled = jax.jit(engine.clipped_grad_fn()).lower(params, batch).compile()
    assert "ghost_norm_sq_pallas" in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 10e9
