"""Statistical density models (Sparseloop Sec. 5.3.2, Table 4), as the
reference evaluates them: the dense, uniform and structured (N:M) models.

Each model answers the two questions the analyzers need about a
fiber/tile of a given shape: ``expected_density(tile_size)`` and
``prob_empty(tile_size)``, plus ``max_nnz`` for capacity checks.  All
probability math is done in log-space (lgamma).
"""
from __future__ import annotations

import dataclasses

from . import num as math


def _log_comb(n: float, k: float) -> float:
    """log C(n, k); -inf when invalid."""
    if k < 0 or k > n or n < 0:
        return -math.inf
    return (math.lgamma(n + 1) - math.lgamma(k + 1) - math.lgamma(n - k + 1))


class DensityModel:
    """Base interface; tile_size is the flattened number of elements."""

    #: fraction of nonzeros in the whole tensor
    density: float
    #: total elements in the tensor this model describes
    tensor_size: int

    def expected_density(self, tile_size: int) -> float:
        return self.density

    def prob_empty(self, tile_size: int) -> float:
        raise NotImplementedError

    def prob_nonempty(self, tile_size: int) -> float:
        return 1.0 - self.prob_empty(tile_size)

    def expected_nnz(self, tile_size: int) -> float:
        return self.expected_density(tile_size) * tile_size

    def max_nnz(self, tile_size: int) -> int:
        """Worst-case nonzeros in a tile (for capacity checks)."""
        return min(tile_size, math.ceil(self.density * self.tensor_size))


@dataclasses.dataclass
class DenseModel(DensityModel):
    tensor_size: int = 1
    density: float = 1.0

    def prob_empty(self, tile_size: int) -> float:
        return 0.0

    def max_nnz(self, tile_size: int) -> int:
        return tile_size


@dataclasses.dataclass
class UniformModel(DensityModel):
    """nnz locations uniformly random: tile nnz ~ Hypergeometric(S, N, T)."""

    tensor_size: int
    density: float

    @property
    def nnz(self) -> int:
        return round(self.density * self.tensor_size)

    def prob_empty(self, tile_size: int) -> float:
        S, N, T = self.tensor_size, self.nnz, min(tile_size, self.tensor_size)
        # P(empty) = C(S-N, T) / C(S, T)
        lp = _log_comb(S - N, T) - _log_comb(S, T)
        return math.exp(lp) if lp > -700 else 0.0

    def max_nnz(self, tile_size: int) -> int:
        return min(tile_size, self.nnz)


@dataclasses.dataclass
class StructuredModel(DensityModel):
    """Fixed N:M structured sparsity along one axis (e.g. 2:4 of the STC).

    Every aligned block of ``m`` elements along the structured axis holds
    exactly ``n`` nonzeros.  For tiles that are multiples of the block the
    behaviour is fully deterministic (this is why Sparseloop reproduces the
    STC's 2x speedup with 100% accuracy — Sec. 6.3.5).
    """

    tensor_size: int
    n: int
    m: int

    @property
    def density(self) -> float:  # type: ignore[override]
        return self.n / self.m

    def expected_density(self, tile_size: int) -> float:
        return self.n / self.m

    def prob_empty(self, tile_size: int) -> float:
        if tile_size >= self.m - self.n + 1:
            # any window of that many elements must contain a nonzero when
            # aligned blocks carry exactly n nonzeros
            return 0.0
        # tile smaller than a block: positions of the n nonzeros within the
        # block are uniform -> hypergeometric within the block
        lp = _log_comb(self.m - self.n, tile_size) - _log_comb(self.m, tile_size)
        return math.exp(lp)

    def max_nnz(self, tile_size: int) -> int:
        full, rem = divmod(tile_size, self.m)
        return min(tile_size, full * self.n + min(rem, self.n))


def make_density_model(spec: object, tensor_size: int) -> DensityModel:
    """Build a model from a workload density spec tuple."""
    if spec is None:
        return DenseModel(tensor_size)
    kind, arg = spec  # type: ignore[misc]
    if kind == "dense":
        return DenseModel(tensor_size)
    if kind == "uniform":
        return UniformModel(tensor_size=tensor_size, density=math.real(arg))
    if kind == "structured":
        return StructuredModel(tensor_size=tensor_size,
                               n=int(arg["n"]), m=int(arg["m"]))
    raise ValueError(f"the reference has no density model {spec!r}")
