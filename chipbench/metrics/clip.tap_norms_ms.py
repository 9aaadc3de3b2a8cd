"""Device milliseconds per step of the per-tap norm work, a part of the norm
pass: the time owned by the ops with ``dp.tap_norm`` in their scope path
(Gram products, instantiated per-sample gradients and their squares, pads,
the ghost-norm kernel) (``chipbench/scopes.py``)."""
from chipbench import scopes

NAME = "clip.tap_norms_ms"


def read(ctx):
    return scopes.stage_ms(ctx, lambda t: t.tap_norm)
