"""Compressed DP gradient sync (shard_map + int8 EF all-gather) vs exact
pmean — runs in a subprocess so the 8-device XLA flag never leaks into this
process (assignment note: tests must see 1 device)."""
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = r"""
import os
# pin the CPU platform: the stripped subprocess env would otherwise let jax
# probe for a TPU runtime (minutes of metadata-server retries off-TPU)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime.grad_sync import compressed_pmean_tree

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
# per-shard local gradients (8, 64, 32): axis 0 = DP shard
g_all = jnp.asarray(rng.standard_normal((8, 64, 32)), jnp.float32)
e0 = jnp.zeros_like(g_all)

def sync(g, e):
    m, ne = compressed_pmean_tree({"w": g[0]}, {"w": e[0]}, "data")
    return m["w"][None], ne["w"][None]

f = jax.shard_map(sync, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data")))
mean_c, err = jax.jit(f)(g_all, e0)
mean_exact = g_all.mean(axis=0)
m0 = np.asarray(mean_c)[0]
rel = np.abs(m0 - np.asarray(mean_exact)).max() / np.abs(mean_exact).max()
assert rel < 0.02, rel
# all shards agree
assert np.allclose(np.asarray(mean_c)[0], np.asarray(mean_c)[7])
# second round with error feedback stays unbiased: mean of (q+err) == g
recon = np.asarray(mean_c).mean(0)
print("OK rel", float(rel))
"""


def test_compressed_grad_sync_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
             "HOME": "/root"}, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK rel" in r.stdout
