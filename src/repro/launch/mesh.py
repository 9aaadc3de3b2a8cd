"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization.  The dry-run entrypoint
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get placeholder devices; smoke tests and benches see 1 real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist, as a (data, model=1) mesh (CPU smoke runs)."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))


def mesh_host_count(mesh: Mesh) -> int:
    """Number of distinct processes owning devices of this mesh.

    The denominator for per-host batch shares (parallel.sharding
    .per_host_batch): memory certificates — the tuner's max-batch search and
    the PR-2 mode re-certification — must be compiled at the slice of the
    batch one host actually materializes, not the global batch no single
    HBM ever holds.
    """
    return len({d.process_index for d in mesh.devices.flat})


def mesh_device_kinds(mesh: Mesh) -> tuple[str, ...]:
    """Sorted distinct ``platform:device_kind`` strings across the mesh.

    More than one entry means a heterogeneous fleet: the clipping autotuner
    then needs the mixed-kind consensus tie-break (repro.tuner.consensus)
    before any rank may trace a tuned branch map.
    """
    return tuple(sorted({
        f"{d.platform}:{d.device_kind}" for d in mesh.devices.flat
    }))
