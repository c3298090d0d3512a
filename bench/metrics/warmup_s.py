"""Seconds of set-up spent building and first-calling every executor the
window uses, ending in ``block_until_ready`` (the serve cell: every batch
size through the runtime), by the benchmark's own span."""


def read(ctx):
    return ctx.spans.get("warmup_s")
