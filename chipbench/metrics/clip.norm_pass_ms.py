"""Device milliseconds per step of the clipping pipeline's first pass: the
time owned by the ops the program runs under the scope ``dp.norm_pass``
(forward, first backward, per-sample norms and clip factors), each instant
going to the innermost op running then (``chipbench/scopes.py``)."""
from chipbench import scopes

NAME = "clip.norm_pass_ms"


def read(ctx):
    return scopes.stage_ms(ctx, lambda t: t.stages["dp.norm_pass"])
