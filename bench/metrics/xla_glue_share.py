"""Share of device-busy time in operations that are no Pallas kernel:
limb/channel conversions, padding, concatenation, jnp bigint helpers."""


def read(ctx):
    glue = ctx.summary.glue_share
    return None if glue is None else 100.0 * glue
