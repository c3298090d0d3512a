"""Seconds of set-up spent in ``race()`` (detection and contraction), by
the benchmark's own span around the call."""


def read(ctx):
    return ctx.spans.get("race_s")
