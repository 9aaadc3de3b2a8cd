"""The ghost-norm kernel's share of its roofline.

The work that one step's ghost-norm calls need is counted from the unpadded
tap shapes: the taps the program routes to the ghost norm (its
``discover_meta`` and ``decide``), each (N, T, D) and (N, T, p).  The least
time for that work is the larger of its FLOPs over the chip's peak and its
bytes over the HBM bandwidth (``chipbench.work``).  The share is that time
over the device time of the kernel's events per step in the trace: custom
calls whose op name holds ``ghost_norm_sq_pallas`` (under ``jit`` and its
transposes alike), not the embedding kernel.  Padding done outside the
kernel is not in its time; padding inside it is.
"""
from chipbench import work

NAME = "ghost_norm_roofline"
KERNEL = "ghost_norm_sq_pallas"


def is_kernel(event_name: str) -> bool:
    op = event_name.split(" = ", 1)[0]
    return KERNEL in op and "embedding" not in op and "custom-call(" in event_name


def read(ctx):
    taps = ctx.trainer.ghost_norm_taps()
    steps = ctx.trace.count_spans("chipbench.sync")
    kernel_s = ctx.trace.op_seconds(is_kernel)
    if not taps or steps == 0 or kernel_s == 0.0:
        return None
    need_s = sum(
        work.roofline_seconds(*work.ghost_norm_work(*tap), ctx.peaks.flops, ctx.peaks.hbm_bw)
        for tap in taps
    )
    return 100.0 * need_s * steps / kernel_s
