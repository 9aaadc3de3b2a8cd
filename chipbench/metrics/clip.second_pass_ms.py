"""Device milliseconds per step of the clipping pipeline's gradient stage:
the time owned by the ops the program runs under the scope
``dp.second_pass`` (the second backward, or the book-keeping einsums)
(``chipbench/scopes.py``)."""
from chipbench import scopes

NAME = "clip.second_pass_ms"


def read(ctx):
    return scopes.stage_ms(ctx, lambda t: t.stages["dp.second_pass"])
