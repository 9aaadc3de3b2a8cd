"""Device milliseconds per step of the relative position tables' norm work:
the time owned by the ops under ``dp.tap_norm/<tap>`` whose tap is a
relative position bias table (the segment sum of each sample's bias
cotangent into the table's rows, and its square), each instant going to the
innermost op running then (``chipbench/scopes.py``).  The other taps' norm
ops are told apart by renaming their scope in the programs' text before
``scopes`` reads it; a program without such a table reads nothing."""
import re

from chipbench import scopes

NAME = "clip.rel_pos_bias_ms"
TABLE = "relative_position_bias_table"
OTHER_TAP = re.compile(r"dp\.tap_norm/(?![^/\";]*" + TABLE + ")")


def read(ctx):
    if not ctx.trace.ops:
        return None
    programs = {name: OTHER_TAP.sub("dp.other_tap/", text)
                for name, text in scopes.compiled_programs(ctx.trainer).items()}
    times = scopes.stage_times(ctx.trace, programs)
    if times is None or times.tap_norm <= 0:
        return None
    return 1e3 * times.tap_norm
