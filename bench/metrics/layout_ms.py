"""Device milliseconds per sweep in RACE's layout passes: every other
operation inside RACE's ``jit_race_*`` modules (transposes, pads, slices,
copies around the kernel), averaged over the chips
(``bench/race_ops.py``)."""
from bench.race_ops import per_sweep_ms


def read(ctx):
    return per_sweep_ms(ctx, 1)
