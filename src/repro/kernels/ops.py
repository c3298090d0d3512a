"""Jitted public entry points for the RACE stencil Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.core.race import RaceResult, race
from repro.lowering import race_stencil_call


def race_stencil(result: RaceResult, env: dict, block_rows: int = 8,
                 block_cols: int = 8):
    """Run a RACE-optimized stencil via the Pallas kernel (compiled on a
    TPU, interpreted on the CPU backend)."""
    fn = partial(race_stencil_call, result.plan, block_rows=block_rows,
                 block_cols=block_cols)
    return jax.jit(fn)(env)


def optimize_and_run(program, env: dict, reassociate: int = 3,
                     block_rows: int = 8, block_cols: int = 8):
    """One-shot: RACE-optimize a stencil program and execute it."""
    res = race(program, reassociate=reassociate)
    return res, race_stencil(res, env, block_rows, block_cols)
