"""Distribution tests on 8 fake CPU devices (subprocess: device count is
locked at first jax init, so the main test process can't host these)."""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.configs.registry import build_model, get_arch
    from repro.launch.mesh import _make_mesh
    from repro.launch.specs import train_batch_specs, materialize
    from repro.launch.steps import (DPTrainConfig, make_train_state,
                                    make_train_step, abstract_train_state)
    from repro.optim import adam, warmup_cosine
    from repro.parallel.sharding import batch_shardings, state_shardings
    from repro.configs.base import ShapeConfig

    mesh = _make_mesh((2, 4), ("data", "model"))
    cfg = get_arch("mixtral-8x7b").reduced()
    model = build_model(cfg)
    opt = adam()
    shape = ShapeConfig("t", 16, 4, "train")

    step = make_train_step(model, opt, warmup_cosine(1e-3, 2, 10),
                           DPTrainConfig(logical_batch=4))
    state = make_train_state(model, jax.random.PRNGKey(0), opt)
    st_sh = state_shardings(model, mesh, cfg, jax.eval_shape(lambda: state))
    state = jax.tree_util.tree_map(jax.device_put, state, st_sh)
    specs = train_batch_specs(cfg, shape, 4)
    batch = materialize(specs, jax.random.PRNGKey(1), vocab=cfg.vocab)
    b_sh = batch_shardings(specs, mesh)
    batch = jax.tree_util.tree_map(jax.device_put, batch, b_sh)

    jit_step = jax.jit(step, in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, None))
    state2, metrics = jit_step(state, batch)
    loss1 = float(metrics["loss"])

    # single-device reference must agree (SPMD correctness)
    ref_step = jax.jit(step)
    host_state = jax.tree_util.tree_map(
        lambda x: jax.device_put(jax.device_get(x), jax.devices()[0]),
        make_train_state(model, jax.random.PRNGKey(0), opt))
    host_batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(jax.device_get(x), jax.devices()[0]), batch)
    _, ref_metrics = ref_step(host_state, host_batch)
    print(json.dumps({
        "loss_sharded": loss1,
        "loss_ref": float(ref_metrics["loss"]),
        "nan": bool(any(jnp.any(jnp.isnan(x))
                    for x in jax.tree_util.tree_leaves(state2["params"]))),
    }))
    """
)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["nan"]
    assert abs(res["loss_sharded"] - res["loss_ref"]) < 5e-4, res


PALLAS_SPLIT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax

    import chip_smoke
    from repro.configs.registry import get_arch
    from repro.kernels import dispatch

    cfg = get_arch("xlstm-350m").reduced()
    out = {}
    for mode in ("mixed_ghost", "bk_mixed"):
        # the Pallas kernels (interpreted here) under the 4-device mesh,
        # against one device: the comparison chip_smoke.py --chips 4 makes
        with dispatch.force_impl("pallas"):
            res = chip_smoke.data_parallel_case(
                cfg, mode, batch_size=4, seq=16, micro=4, devices=jax.devices()
            )
        (loss4, grads4, norms4, _), (loss1, grads1, norms1, _) = res["mesh"], res["one"]
        grad, leaves = chip_smoke.grad_errors(grads4, grads1)
        out[mode] = {
            "loss": abs(loss4 - loss1),
            "grad": grad,
            "leaf": max(leaves.values()),
            "norms": float(abs(norms4 - norms1).max()),
            "devices": len(res["held"]),
        }
    print(json.dumps(out))
    """
)


def test_pallas_kernels_split_over_the_mesh():
    """The clipping kernels run per device under a mesh (GSPMD cannot
    partition them): clipped grads on 4 devices equal one device's."""
    out = subprocess.run(
        [sys.executable, "-c", PALLAS_SPLIT_SCRIPT],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={"PYTHONPATH": f"{ROOT}:{ROOT}/src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for mode, err in res.items():
        assert err["devices"] == 4, (mode, err)
        assert err["loss"] < 1e-5 and err["grad"] < 1e-4, (mode, err)
        assert err["leaf"] < 1e-4 and err["norms"] < 1e-4, (mode, err)
