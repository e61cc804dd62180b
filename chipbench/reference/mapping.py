"""Mapping representation (Sparseloop Sec. 5.1 'Mapping').

A mapping is a loop nest (outermost first).  Each loop is bound to a
storage level: temporal loops at level s iterate over sub-tiles that are
delivered into level s-1 (coordinate-space tiling, Sec. 5.2 / Fig. 7a);
spatial loops at level s distribute sub-tiles across the fanout of
hardware instances *below* level s.

Levels use innermost-first indices: 0 = innermost storage (e.g. RF),
num_levels-1 = outermost (e.g. DRAM).
"""
from __future__ import annotations

import dataclasses

from . import num as math
from .workload import Workload


@dataclasses.dataclass(frozen=True)
class Loop:
    rank: str
    bound: int
    level: int            # storage level (innermost-first index) it lives at
    spatial: bool = False


@dataclasses.dataclass(frozen=True)
class LoopNest:
    """Ordered outermost -> innermost."""

    loops: tuple[Loop, ...]
    num_levels: int

    # ------------------------------------------------------------------
    def validate(self, workload: Workload) -> None:
        prod: dict[str, int] = {r: 1 for r in workload.rank_bounds}
        for lp in self.loops:
            if lp.rank not in prod:
                raise ValueError(f"loop over unknown rank {lp.rank}")
            if not (0 <= lp.level < self.num_levels):
                raise ValueError(f"loop level {lp.level} out of range")
            prod[lp.rank] *= lp.bound
        for r, b in workload.rank_bounds.items():
            if prod[r] != b:
                raise ValueError(
                    f"rank {r}: mapped product {prod[r]} != bound {b}")
        # loops must be grouped by non-increasing level (outermost first),
        # with spatial loops allowed anywhere within their level's group
        levels = [lp.level for lp in self.loops]
        if levels != sorted(levels, reverse=True):
            raise ValueError("loops must be ordered outermost level first")

    # ------------------------------------------------------------------
    def tile_bounds(self, level: int) -> dict[str, int]:
        """Per-rank extents of the tile RESIDENT at `level`.

        Includes every loop at levels <= level (its own temporal loops
        iterate sub-tiles *within* the resident tile, so they count), i.e.
        the data footprint needed to execute the whole sub-nest at or
        below this level.
        """
        out: dict[str, int] = {}
        for lp in self.loops:
            if lp.level <= level:
                out[lp.rank] = out.get(lp.rank, 1) * lp.bound
        return out

    def spatial_loops_at(self, level: int) -> tuple[Loop, ...]:
        return tuple(lp for lp in self.loops
                     if lp.spatial and lp.level == level)

    def fanout_below(self, level: int) -> int:
        """Hardware instances of level-1 storage under one level instance."""
        return math.prod(lp.bound for lp in self.spatial_loops_at(level))

    def instances_of(self, level: int) -> int:
        """Total instances of `level` storage in the machine."""
        return math.prod(lp.bound for lp in self.loops
                         if lp.spatial and lp.level > level)


# ----------------------------------------------------------------------
# Convenience constructors
# ----------------------------------------------------------------------
def nest(num_levels: int, *specs: tuple) -> LoopNest:
    """Build a LoopNest from (rank, bound, level[, 'spatial']) tuples,
    listed outermost first."""
    loops = []
    for s in specs:
        rank, bound, level = s[0], s[1], s[2]
        spatial = len(s) > 3 and s[3] == "spatial"
        loops.append(Loop(rank=rank, bound=int(bound), level=int(level),
                          spatial=spatial))
    return LoopNest(loops=tuple(loops), num_levels=num_levels)


