"""Roofline share of the `rns_mont_exp` kernel: the least time its counted work
could take (`bench.counts.roofline_seconds`) over its trace time."""
from bench import counts

KERNEL = "rns_mont_exp"


def read(ctx):
    seconds = ctx.summary.kernel_s.get(KERNEL)
    work = ctx.work[KERNEL]
    if not seconds or ctx.peak is None or work.products == 0:
        return None
    least = counts.roofline_seconds(work, ctx.peak) * ctx.iterations
    return 100.0 * least / seconds
