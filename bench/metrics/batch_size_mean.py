"""Requests per coalesced dispatch of the serve runtime over the window:
``ServeRuntime.stats()`` deltas, completed / batches."""


def read(ctx):
    batches = ctx.counters.get("batches", 0)
    if not batches:
        return None
    return ctx.counters["completed"] / batches
