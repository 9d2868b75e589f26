"""The counted modular products of the traced iterations at their
lower-bound operations (`bench.counts`), over the device-busy seconds of
the trace times the chip's int8 peak: the device's share of its peak
while it works, read from the trace alone (idle time is `idle_share`'s)."""
from bench import counts


def read(ctx):
    if ctx.peak is None or ctx.summary.busy_s <= 0.0:
        return None
    ops = counts.total_ops(ctx.work) * ctx.iterations
    return 100.0 * ops / (ctx.summary.busy_s * ctx.peak["int8_ops_per_s"])
