"""Device milliseconds per sweep in RACE's Pallas kernels: the operations
named ``race_kernel_<program>`` inside RACE's modules, averaged over the
chips (``bench/race_ops.py``)."""
from bench.race_ops import per_sweep_ms


def read(ctx):
    return per_sweep_ms(ctx, 0)
