"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` before any jax initialization.
"""
from __future__ import annotations

import jax


def _axis_type_kwargs(n_axes: int) -> dict:
    return dict(axis_types=(jax.sharding.AxisType.Auto,) * n_axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis (extra data parallelism across the inter-pod DCN/ICI links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / small runs (e.g. (2, 2) on 4 host devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_type_kwargs(len(axes)))


def stencil_mesh_shape(n: int, k: int) -> tuple:
    """Factor ``n`` devices into ``k`` near-square mesh dims, largest first.

    Mirrors the ``models/sharding.py:_fit`` divisibility discipline: every
    dim is an exact divisor of ``n`` by construction, so a product over any
    axis subset always divides the device count.  Per trailing axis we take
    the largest divisor no bigger than the remaining count's k-th root:
    8 -> (4, 2), 4 -> (2, 2), 6 -> (3, 2), primes degrade to (n, 1, ...).
    """
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if k < 1:
        raise ValueError(f"need at least one mesh axis, got {k}")
    dims = []
    for remaining in range(k, 1, -1):
        root = n ** (1.0 / remaining)
        d = max(f for f in range(1, int(root + 1e-9) + 1) if n % f == 0)
        dims.append(d)
        n //= d
    dims.append(n)
    return tuple(sorted(dims, reverse=True))


def make_stencil_mesh(n_devices=None, axes=("sx", "sy")):
    """Near-square spatial mesh over the first ``n_devices`` host devices.

    The sharded executor (``repro.shard``) partitions a plan's iteration box
    over this mesh; CPU CI forces host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and carves
    1/2/4/8-device submeshes out of the same process for scaling rows, which
    is why this builds over a device *subset* rather than ``jax.make_mesh``'s
    all-devices contract.
    """
    import numpy as np

    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"n_devices={n} out of range for {len(devs)} visible device(s)")
    axes = tuple(axes)
    shape = stencil_mesh_shape(n, len(axes))
    return jax.sharding.Mesh(np.asarray(devs[:n]).reshape(shape), axes)


#: Published per-chip peaks keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
#: HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four
#: links).
PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9,
                        ici_bw_per_link=50e9),
}


def chip_peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of a device kind; an unlisted kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"repro.launch.mesh.PEAKS with its source") from None
