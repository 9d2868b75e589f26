"""Share of the traced window in which the device is idle while the host
is outside every backend call (encrypt_share, matvec, add_mask,
decrypt_to_r64): the time the conductor and the protocol legs' host work
holds the chip back, from the harness's own annotations in the trace."""


def read(ctx):
    return 100.0 * ctx.summary.host_share
