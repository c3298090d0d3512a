"""Percent of the traced window in which no operation ran on the device,
averaged over the chips (sweep cells)."""
from bench.trace import idle_share


def read(ctx):
    share = idle_share(ctx.trace)
    return None if share is None else 100.0 * share
