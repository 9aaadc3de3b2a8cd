"""Share of the traced window in which no operation ran on the device:
1 - (union of the op intervals on each ``/device:TPU:<n>`` plane) / window,
averaged over the chips.  The window runs from the first harness span's
start to the last one's end."""
NAME = "device.idle_share"


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
