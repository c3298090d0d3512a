"""Candidate-space enumeration for the autotuner.

The paper leaves its biggest knob — the reassociation strategy — open at
apply time ("with various aggressive strategies", Section 7); our port adds
two more: the execution backend and the Pallas block configuration.  This
module enumerates the product space for one program + environment signature:

    reassociate ∈ {0, 3, 4}            (the levels the repo implements)
  × backend     ∈ {xla} ∪ {pallas if the capability probe passes}
  × blocks      ∈ a small per-plan grid of (block_rows, block_cols,
                   block_inner) — block_inner > 0 grid-tiles the innermost
                   level for very wide rows (0 keeps it full-width, the
                   default the kernel has always used)

The space is deliberately small: every candidate is *measured* (warmup +
repeats through the compiled-executor path) and correctness-gated, so the
search cost is candidates x repeats real executions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Mapping, Optional, Sequence

from repro.core.backend import probe_pallas
from repro.core.depgraph import Plan

#: the reassociation strategies the repo implements (paper Section 7.1)
REASSOCIATE_LEVELS = (0, 3, 4)

#: representative serving batch sizes for batch-aware tuning: the batched
#: (vmapped) executor has different economics from the per-call path —
#: dispatch overhead amortizes, Pallas block choices interact with the
#: leading vmap axis — so the tuner measures these sizes separately and the
#: serving runtime picks the nearest recorded one at dispatch time.
DEFAULT_BATCH_SIZES = (2, 8, 32)


def representative_batch_sizes(quick: bool = False) -> tuple:
    """The batch sizes a batch-aware search measures (one in quick mode)."""
    return (8,) if quick else DEFAULT_BATCH_SIZES


@dataclass(frozen=True)
class Config:
    """One point of the search space (hashable; the tuner's unit of work)."""

    reassociate: int
    backend: str  # "xla" | "pallas"
    block_rows: int = 8
    block_cols: int = 8
    block_inner: int = 0  # 0 = innermost level full-width

    def describe(self) -> str:
        if self.backend != "pallas":
            return f"r{self.reassociate}/{self.backend}"
        inner = self.block_inner or "full"
        return (f"r{self.reassociate}/pallas"
                f"[{self.block_rows}x{self.block_cols}x{inner}]")

    def as_dict(self) -> dict:
        return dict(reassociate=self.reassociate, backend=self.backend,
                    block_rows=self.block_rows, block_cols=self.block_cols,
                    block_inner=self.block_inner)

    @classmethod
    def from_dict(cls, d: Mapping) -> "Config":
        return cls(reassociate=int(d["reassociate"]),
                   backend=str(d["backend"]),
                   block_rows=int(d.get("block_rows", 8)),
                   block_cols=int(d.get("block_cols", 8)),
                   block_inner=int(d.get("block_inner", 0)))


def block_grid(plan: Plan, quick: bool = False) -> List[tuple]:
    """A small per-plan grid of (block_rows, block_cols, block_inner).

    Always includes the static default (8, 8, 0).  Extra points are added
    only where the plan's extents make them meaningful — generic over nest
    depth since the lowering engine closed the envelope: a taller row block
    when level 1 has room, a wider column block when any middle level
    (2..m-1) has room, and an innermost tile when the last level is wide
    enough to split into lane-aligned tiles (multiples of 128, the TPU's
    lane width).  A 1-D nest's single level is its lane axis and stays one
    whole block, so it has no extra points.
    """
    prog = plan.program
    m = prog.depth
    ranges = prog.ranges()
    extents = [ranges[l][1] - ranges[l][0] + 1 for l in range(1, m + 1)]
    grid = [(8, 8, 0)]
    if m >= 2 and extents[0] > 8:
        grid.append((16, 8, 0))
    if not quick and m >= 3 and any(e > 8 for e in extents[1:-1]):
        grid.append((8, 16, 0))
    inner = extents[-1]
    if m >= 2 and inner >= 256:
        # one lane-aligned tile that splits the row at least twice
        grid.append((8, 8, 128 * max(1, inner // 512)))
    return grid


def candidate_configs(plans: Mapping[int, Plan],
                      backends: Optional[Sequence[str]] = None,
                      grid: Optional[Iterable[tuple]] = None,
                      quick: bool = False) -> List[Config]:
    """Enumerate every (reassociate level, backend, blocks) candidate.

    ``plans`` maps each reassociation level to its finalized plan.  XLA is
    always eligible; Pallas only where the capability probe passes *for that
    level's plan* (reassociation can change eligibility — e.g. by splitting
    auxiliary statements).  ``backends`` restricts the set (e.g. ``("xla",)``
    for a cheap search); ``grid`` overrides the per-plan block grid.
    """
    allowed = tuple(backends) if backends is not None else ("xla", "pallas")
    out: List[Config] = []
    for lvl in sorted(plans):
        plan = plans[lvl]
        if "xla" in allowed:
            out.append(Config(lvl, "xla"))
        if "pallas" in allowed and probe_pallas(plan).eligible:
            for br, bc, bi in (grid if grid is not None
                               else block_grid(plan, quick)):
                out.append(Config(lvl, "pallas", br, bc, bi))
    return out
