"""The designs of the benchmark's configurations, as the reference builds
them: the three-level Eyeriss-like hierarchy under SCNN's SAFs (Sparseloop
Table 3), and the TPU v5e hierarchy with and without N:M weight
compression.  Energy numbers are Accelergy-style 45nm-class per-action
costs (pJ/16-bit word); the TPU numbers are per chip.

A configuration file names a design as ``{"preset": ..., "arch": ...}``
and, for N:M, ``{"n": ..., "m": ...}``: :func:`design` builds it.
"""
from __future__ import annotations

import dataclasses

from .arch import Architecture, ComputeLevel, StorageLevel
from .taxonomy import ActionSAF, RankFormat, SAFKind, SAFSpec, TensorFormat

INF = float("inf")


@dataclasses.dataclass(frozen=True)
class Design:
    """Architecture x SAFs."""

    arch: Architecture
    safs: SAFSpec
    name: str = ""

    @property
    def level_names(self) -> list[str]:
        """Innermost-first storage level names (mapping level indices)."""
        return [self.arch.level(s).name for s in range(self.arch.num_levels)]


def three_level_arch(name: str = "eyeriss-like", glb_kwords: float = 96,
                     spad_words: int = 512, pes: int = 168) -> Architecture:
    return Architecture(
        name=name,
        levels=(
            StorageLevel("DRAM", INF, 16, 200.0, 200.0, 0.0),
            StorageLevel("GLB", glb_kwords * 1024, 128, 6.0, 6.0, 0.05),
            StorageLevel("SPad", spad_words, 2 * pes, 1.2, 1.2, 0.02),
        ),
        compute=ComputeLevel("MAC", instances=pes, mac_energy_pj=1.0,
                             gated_energy_pj=0.05),
    )


def tpu_v5e_arch() -> Architecture:
    """Per-chip numbers: 197 TFLOP/s bf16, 819 GB/s HBM, at the cycle
    granularity of the 940 MHz clock; words are bf16.  REG models the
    MXU's in-array accumulators."""
    clock_hz = 0.94e9
    hbm_words_per_cycle = 819e9 / 2 / clock_hz
    vmem_words_per_cycle = 8192.0
    macs = 197e12 / 2 / clock_hz
    return Architecture(
        name="tpu-v5e",
        levels=(
            StorageLevel("HBM", 16e9 / 2, hbm_words_per_cycle, 80.0, 80.0,
                         0.0),
            StorageLevel("VMEM", 64e6, vmem_words_per_cycle, 1.5, 1.5, 0.02),
            StorageLevel("REG", 8192, 64.0, 0.05, 0.05, 0.005),
        ),
        compute=ComputeLevel("MXU", instances=int(macs), mac_energy_pj=0.4,
                             gated_energy_pj=0.02),
    )


ARCHS = {"three_level_arch": three_level_arch, "tpu_v5e_arch": tpu_v5e_arch}


def dense_design(arch: Architecture) -> Design:
    """No SAFs: the dense baseline."""
    return Design(arch=arch, safs=SAFSpec(), name="dense")


def scnn_like(arch: Architecture) -> Design:
    """SCNN (Table 3): I/W in B-UOP-RLE, skip W<-I and O<-I&W at innermost
    storage, Gate Compute."""
    fmt = TensorFormat.of(RankFormat.UOP, RankFormat.RLE, coord_bits=4)
    safs = SAFSpec(
        formats={
            ("GLB", "A"): fmt, ("GLB", "B"): fmt,
            ("SPad", "A"): fmt, ("SPad", "B"): fmt,
        },
        actions=(
            ActionSAF(SAFKind.SKIP, "SPad", "B", ("A",)),
            ActionSAF(SAFKind.SKIP, "SPad", "Z", ("A", "B")),
            ActionSAF(SAFKind.GATE, "compute", "Z", ("A", "B")),
        ))
    return Design(arch=arch, safs=safs, name="scnn-like")


def nm_weights(arch: Architecture, n: int, m: int) -> Design:
    """N:M-pruned weights (tensor B, the (K, N) operand) CP-compressed in
    HBM and VMEM, decompressed in front of a dense MXU: no skipping."""
    coord_bits = max(1, (m - 1).bit_length())
    fmt = TensorFormat.of(RankFormat.CP, coord_bits=coord_bits)
    return Design(arch=arch,
                  safs=SAFSpec(formats={("HBM", "B"): fmt,
                                        ("VMEM", "B"): fmt}, actions=()),
                  name=f"tpu-nm-{n}:{m}")


def design(spec: dict) -> Design:
    """The design a configuration file names."""
    arch = ARCHS[spec["arch"]]()
    kind = spec["preset"]
    if kind == "dense_design":
        return dense_design(arch)
    if kind == "scnn_like":
        return scnn_like(arch)
    if kind == "nm_weights":
        return nm_weights(arch, int(spec["n"]), int(spec["m"]))
    raise ValueError(f"the reference has no design {kind!r}")
