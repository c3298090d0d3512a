"""Share of the bandwidth roofline, in percent: the sweep's compulsory HBM
bytes over the chip's peak bandwidth, divided by the device's busy time
per sweep.  Busy time is the union of every device operation of the
window, not of named kernels only; on several chips the bytes and the time
are per chip, on the busiest chip.  The f32 stencils need 1-3 FLOP a byte
and the v5e publishes no f32 peak, so the bound is bytes only."""
from bench.peaks import peaks
from bench.trace import busy_s


def read(ctx):
    busy = busy_s(ctx.trace)
    if not ctx.sweeps or not busy or max(busy.values()) <= 0:
        return None
    least = ctx.sweep_bytes / ctx.chips / peaks(ctx.device_kind)[
        "hbm_bytes_per_s"]
    return 100.0 * least / (max(busy.values()) / ctx.sweeps)
