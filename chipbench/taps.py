"""The program's own view of its taps, read for the kernel rooflines."""
from __future__ import annotations

import jax.numpy as jnp


def ghost_norm_taps(loss_with_ctx, params_spec, batch_spec, mode: str) -> list[tuple]:
    """(N, T, D, p, a bytes, g bytes) of each matmul tap that the program's
    tap discovery and branch decision send to the ghost norm under ``mode``."""
    from repro.core.clipping import discover_meta
    from repro.core.decision import decide

    if mode == "non_private":
        return []
    out = []
    for meta in discover_meta(loss_with_ctx, params_spec, batch_spec).values():
        if meta.kind == "matmul" and decide(meta, mode=mode) == "ghost":
            rows = meta.batch_size * meta.n_stack * max(meta.n_groups, 1)
            a_dtype = jnp.dtype(meta.a_dtype if meta.a_dtype is not None else meta.s_dtype)
            out.append((rows, meta.T, meta.D, meta.p, a_dtype.itemsize,
                        jnp.dtype(meta.s_dtype).itemsize))
    return out
