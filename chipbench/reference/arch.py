"""Architecture specification (Sparseloop Sec. 5.1, Fig. 6 'Architecture').

An architecture is a linear hierarchy of storage levels (outermost, e.g.
DRAM, to innermost, e.g. register file) plus a set of compute units.  Each
storage level has a capacity, word width, access bandwidth and per-action
energy numbers (Accelergy-style, Sec. 5.4).

Levels are indexed the way the analyzers use them: 0 = innermost.
"""
from __future__ import annotations

import dataclasses

from . import num


@dataclasses.dataclass(frozen=True)
class StorageLevel:
    name: str
    #: capacity in data words (inf for DRAM)
    capacity_words: float
    #: sustained words per cycle into/out of the level
    bandwidth_words_per_cycle: float
    #: energy per word read/write, pJ (Accelergy-style action cost)
    read_energy_pj: float
    write_energy_pj: float = -1.0
    #: energy of a *gated* access (clock/power-gated idle), pJ
    gated_energy_pj: float = 0.0
    #: per-word energy of metadata accesses (usually narrower words)
    metadata_read_energy_pj: float = -1.0
    #: bits per data word (used for compression-rate accounting)
    word_bits: int = 16

    def __post_init__(self):
        num.hold_reals(self, ("capacity_words", "bandwidth_words_per_cycle",
                              "read_energy_pj", "write_energy_pj",
                              "gated_energy_pj", "metadata_read_energy_pj"))
        if self.write_energy_pj < 0:
            object.__setattr__(self, "write_energy_pj", self.read_energy_pj)
        if self.metadata_read_energy_pj < 0:
            object.__setattr__(self, "metadata_read_energy_pj",
                               0.25 * self.read_energy_pj)


@dataclasses.dataclass(frozen=True)
class ComputeLevel:
    name: str = "MAC"
    #: spatial compute instances
    instances: int = 1
    #: energy per effectual MAC, pJ
    mac_energy_pj: float = 1.0
    #: energy per gated (idle) MAC cycle, pJ
    gated_energy_pj: float = 0.05
    #: MACs per instance per cycle
    throughput: float = 1.0

    def __post_init__(self):
        num.hold_reals(self, ("mac_energy_pj", "gated_energy_pj",
                              "throughput"))


@dataclasses.dataclass(frozen=True)
class Architecture:
    """Storage hierarchy listed OUTERMOST FIRST (DRAM ... RF) + compute."""

    name: str
    levels: tuple[StorageLevel, ...]
    compute: ComputeLevel = ComputeLevel()

    @property
    def num_levels(self) -> int:
        return len(self.levels)

    def level(self, idx_from_inner: int) -> StorageLevel:
        """Level by innermost-first index (0 = closest to compute)."""
        return self.levels[self.num_levels - 1 - idx_from_inner]
