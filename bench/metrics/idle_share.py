"""1 − (union of device operation intervals) / traced window."""


def read(ctx):
    if ctx.summary.busy_s <= 0.0:
        return None
    return 100.0 * ctx.summary.idle_share
