"""Device milliseconds per step of the noise and the optimizer update: the
time owned by the ops the program runs under the scopes ``dp.noise`` and
``dp.update`` (``chipbench/scopes.py``)."""
from chipbench import scopes

NAME = "loop.noise_update_ms"


def read(ctx):
    return scopes.stage_ms(ctx, lambda t: t.noise_update)
