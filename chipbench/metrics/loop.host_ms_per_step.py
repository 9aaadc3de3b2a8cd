"""Host milliseconds per step outside the wait for the device.

The harness wraps each call of its loop in a ``chipbench.*`` span on the
profiler's clock: making the batch, dispatching the step, waiting for it
(``sync``) and the privacy accounting (``record_step``).  This is the sum of
the spans other than ``sync``, over the steps of the traced window: the host
work that the device waits for unless it overlaps the previous step.
"""
NAME = "loop.host_ms_per_step"
HOST_SPANS = ("chipbench.make_batch", "chipbench.dispatch", "chipbench.record_step")


def read(ctx):
    steps = ctx.trace.count_spans("chipbench.sync")
    if steps == 0:
        return None
    return 1e3 * sum(ctx.trace.span_seconds(s) for s in HOST_SPANS) / steps
