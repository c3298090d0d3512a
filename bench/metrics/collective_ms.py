"""Device milliseconds per sweep of collective operations (the halo
collective permutes and any resharding), averaged over the chips."""
from bench.trace import collective_s


def read(ctx):
    coll = collective_s(ctx.trace)
    if not ctx.sweeps or not coll or ctx.chips < 2:
        return None
    return 1e3 * sum(coll.values()) / len(coll) / ctx.sweeps
