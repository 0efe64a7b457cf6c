"""pallas-budget: static VMEM and tiling audit of the Pallas kernels.

A Mosaic VMEM allocation failure is among the most expensive bug classes
this repo has: it surfaces minutes into a chip run, after the warmup
sweep, as an opaque runtime error.  The
kernels' per-grid-cell VMEM residency is fully determined by their
BlockSpecs — static data — so it can be costed on CPU in microseconds.

`ops.pallas_segment.kernel_vmem_blocks` (kept next to the kernels, so a
tiling change and its budget model move in one diff) describes what each
kernel keeps resident per grid cell; this rule costs that inventory at
every serve-ladder bucket × the model feature widths and flags anything
over the per-core VMEM budget.  The fused SAGE kernel is the reason this
exists: its message block is *full height* ([N_pad, TF] f32, double-
buffered), so its footprint grows linearly with the node bucket — fine at
the deployed 4096-node rung (~2 MiB), over budget somewhere past 16k
nodes, and a learned-ladder tuner (ROADMAP) could propose exactly such a
rung.  Also checks grid divisibility: every tile constant must respect
the (8, 128) f32 tiling and divide its padded extent.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from nerrf_tpu.analysis.engine import Finding, Rule
from nerrf_tpu.analysis.programs.abstract import finding

_PALLAS_PATH = "nerrf_tpu/ops/pallas_segment.py"

# per-core VMEM on the TPU generations in scope (v4/v5e: 16 MiB; v5p is
# larger — the floor is the portable budget)
DEFAULT_VMEM_BYTES = 16 << 20

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int32": 4, "int64": 8,
             "bool": 1, "float16": 2}


def block_bytes(blocks) -> int:
    """Total VMEM residency of one kernel's block inventory."""
    total = 0
    for _name, shape, dtype, copies in blocks:
        n = 1
        for d in shape:
            n *= int(d)
        total += n * _ITEMSIZE.get(str(dtype), 4) * int(copies)
    return total


class PallasBudget(Rule):
    id = "pallas-budget"
    description = ("Pallas block shapes × dtype vs the per-core VMEM "
                   "budget, and tile/grid divisibility, at ladder shapes")
    deep = True

    def __init__(self, vmem_bytes: int = DEFAULT_VMEM_BYTES,
                 shapes: Optional[List[Tuple[int, int, int]]] = None) -> None:
        self._budget = int(vmem_bytes)
        self._shapes = shapes

    def _ladder_shapes(self) -> List[Tuple[int, int, int]]:
        """(nodes, edges, features) audit points: every serve bucket at
        the widest feature extent the model runs the kernels at."""
        from nerrf_tpu.graph.builder import NODE_FEATURE_DIM
        from nerrf_tpu.models import GraphSAGEConfig
        from nerrf_tpu.serve.config import ServeConfig

        width = max(GraphSAGEConfig().hidden, NODE_FEATURE_DIM)
        return [(n, e, width) for n, e, _s in ServeConfig().buckets]

    def run(self, project) -> List[Finding]:
        from nerrf_tpu.ops.pallas_segment import (
            kernel_vmem_blocks,
            tile_constants,
        )

        out: List[Finding] = []
        tiles = tile_constants()
        # TN and TF appear as LANE extents (the one-hot blocks are
        # (TE, TN); data/out blocks are (·, TF)) → multiples of 128;
        # TE only ever tiles the sublane axis → multiple of 8
        lane_mult = {"TN": 128, "TE": 8, "TF": 128}
        for name, t in tiles.items():
            mult = lane_mult.get(name, 128)
            if t % mult:
                out.append(finding(
                    self.id, _PALLAS_PATH, 1,
                    anchor=f"pallas:tile:{name}",
                    message=f"tile constant {name}={t} is not a "
                            f"multiple of {mult} — violates the "
                            f"(8, 128) f32 register tiling for the axes "
                            f"it spans",
                    hint="keep lane-extent tiles (TN, TF) multiples of "
                         "128 and sublane tiles (TE) multiples of 8"))
        shapes = self._shapes if self._shapes is not None \
            else self._ladder_shapes()
        for n, e, f in shapes:
            out.extend(self.audit(kernel_vmem_blocks(n, e, f),
                                  shape=(n, e, f)))
        return out

    def audit(self, inventories: dict, shape=None,
              budget: Optional[int] = None) -> List[Finding]:
        """Cost one ``{kernel: blocks}`` inventory against the budget —
        the fixture surface (tests feed synthetic inventories here)."""
        budget = self._budget if budget is None else int(budget)
        tag = "x".join(str(s) for s in shape) if shape else "fixture"
        out: List[Finding] = []
        for kernel, blocks in inventories.items():
            total = block_bytes(blocks)
            if total > budget:
                biggest = max(
                    blocks, key=lambda b: block_bytes([b]))
                out.append(finding(
                    self.id, _PALLAS_PATH, 1,
                    anchor=f"pallas:{kernel}:{tag}:vmem",
                    message=f"{kernel} at shape {tag}: "
                            f"{total / (1 << 20):.1f} MiB VMEM resident "
                            f"per grid cell exceeds the "
                            f"{budget / (1 << 20):.0f} MiB budget "
                            f"(dominant block: {biggest[0]} "
                            f"{biggest[1]} {biggest[2]} "
                            f"×{biggest[3]})",
                    hint="shrink the dominant block (tile the full-"
                         "height msg block, or cap the ladder rung) — "
                         "on chip this is a Mosaic allocation failure "
                         "minutes into a queue step"))
            for bname, bshape, _dtype, _copies in blocks:
                lanes = bshape[-1] if bshape else 0
                if len(bshape) >= 2 and lanes >= 128 and lanes % 128:
                    out.append(finding(
                        self.id, _PALLAS_PATH, 1,
                        anchor=f"pallas:{kernel}:{bname}:lanes",
                        message=f"{kernel}: block {bname} lane extent "
                                f"{lanes} is not a multiple of 128",
                        hint="pad the feature extent to the 128-lane "
                             "register shape"))
        return out
