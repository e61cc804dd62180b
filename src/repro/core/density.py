"""Statistical density models (Sparseloop Sec. 5.3.2, Table 4).

Each model characterizes the distribution of nonzero locations in a tensor
and answers the two questions the analyzers need about a *fiber/tile* of a
given shape (Fig. 9 of the paper):

  * ``expected_density(tile_size)``  — E[nnz(tile)] / tile_size
  * ``prob_empty(tile_size)``        — P(tile is all zeros)
  * ``expected_nnz / max_nnz``       — for format-overhead & capacity checks

Supported models (Table 4):

  dense            : density 1 everywhere.
  uniform          : nnz placed uniformly at random (hypergeometric tiles).
                     Coordinate independent.
  structured (N:M) : exactly N nonzeros per aligned block of M along one
                     axis (2:4 STC-style).  Coordinate independent,
                     deterministic at granularity M.
  banded           : nonzeros within +/- half_band of the diagonal of a 2-D
                     tensor.  Coordinate *dependent*.
  actual           : wraps a concrete numpy array; exact empirical tile
                     statistics.  Coordinate dependent, non-statistical.
  selected         : exactly k of L fibres hold data, the same k along
                     the whole dense rank (a top-k selection, e.g.
                     DeepSeek Sparse Attention's selected cache tokens).

All prob/expectation math is done in log-space (lgamma) so it is both
numerically stable and usable from inside jitted/vmapped mapper code.

Traced parametric interface (workload-as-data)
----------------------------------------------
Every model also lowers to a *fixed-shape parameter vector*
(:meth:`DensityModel.params`, ``NUM_DENSITY_PARAMS`` floats) plus a
small integer ``kind_id``, and each statistic has a static traced form
``<kind>_<stat>_t(params, hist, tile_size)`` whose inputs are all JAX
values.  :class:`TracedDensityStats` bundles them behind one runtime
``lax.switch`` on the model id, so a single compiled program serves
tensors (and whole network layers) of *mixed* density kinds — the
model parameters ride as traced data instead of trace-time constants.

The ``actual``-data model — which used to be scalar-only because it
iterates a concrete numpy array — lowers through a per-tensor
*tile-occupancy histogram* (:meth:`ActualDataModel.hist_table`):
``(3, tensor_size)`` exact ``(prob_empty, expected_density, max_nnz)``
rows for every aligned 1-D tile size, precomputed once from the array
(O(n log n) via a cumulative-sum sweep) and gathered by traced tile
size at evaluation time.  Shape-dependent statistics (banded row scans,
histogram tables) are padded to static :class:`DensityCaps` so programs
stay shape-stable across layers.

The uniform and structured ``prob_empty`` forms share one hypergeometric
term, ``exp(log C(a - b, t) - log C(a, t))``.  The switch returns that
term's arguments and the term itself is evaluated outside it, so a whole
traced program can evaluate every such term in ONE ``gammaln``/``exp``
op (:class:`HyperBatch`).  The TPU has no float64 unit: it emulates each
float64 ``lgamma`` with a long expansion that costs seconds of compile
per call site, and a bucket program has hundreds of sites.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

#: density-model kind ids (the ``lax.switch`` index of TracedDensityStats)
DENSE_ID, UNIFORM_ID, STRUCTURED_ID, BANDED_ID, ACTUAL_ID, SELECTED_ID = \
    range(6)
MODEL_KINDS = ("dense", "uniform", "structured", "banded", "actual",
               "selected")

#: fixed length of every model's traced parameter vector
NUM_DENSITY_PARAMS = 4


def _log_comb(n: float, k: float) -> float:
    """log C(n, k); -inf when invalid."""
    if k < 0 or k > n or n < 0:
        return -math.inf
    return (math.lgamma(n + 1) - math.lgamma(k + 1) - math.lgamma(n - k + 1))


def _log_comb_args(n, k):
    """The three ``gammaln`` arguments of log C(n, k)."""
    return (n + 1.0, k + 1.0, n - k + 1.0)


def _log_comb_of(n, k, g):
    """log C(n, k) from ``g``, the ``gammaln`` of its three arguments;
    -inf when invalid."""
    import jax.numpy as jnp
    valid = (k >= 0) & (k <= n) & (n >= 0)
    return jnp.where(valid, g[0] - g[1] - g[2], -jnp.inf)


class BatchedDensityUnsupported(NotImplementedError):
    """Raised when a density model has no closed-form batched (JAX) path.

    Every Table-4 model (actual-data included, via its tile-occupancy
    histogram) now has a traced form, so this is only raised for unknown
    specs; it is kept for API compatibility with callers that still
    guard the batched dispatch.
    """


# ----------------------------------------------------------------------
# Static capacities for the shape-dependent traced statistics
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DensityCaps:
    """Static padding capacities of a traced density program.

    Traced programs need static array shapes; coordinate-dependent
    statistics don't have any.  The caps bound them: ``coord`` >= the
    row count of any banded tensor (row-scan length), ``div`` >= the
    isqrt of any banded tensor's size (tile-shape divisor scan), and
    ``hist`` >= the size of any actual-data tensor (histogram table
    length), and ``select`` is 1 where some tensor is a selection.  Zero
    means "no tensor of that family" and prunes the corresponding
    ``lax.switch`` branch entirely.  Caps are part of a
    compiled program's cache key; :func:`caps_for_models` rounds them up
    to powers of two so layers of similar size land on the same program.
    """

    coord: int = 0
    div: int = 0
    hist: int = 0
    select: int = 0

    def merge(self, other: "DensityCaps") -> "DensityCaps":
        return DensityCaps(coord=max(self.coord, other.coord),
                           div=max(self.div, other.div),
                           hist=max(self.hist, other.hist),
                           select=max(self.select, other.select))

    def covers(self, need: "DensityCaps") -> bool:
        return (self.coord >= need.coord and self.div >= need.div
                and self.hist >= need.hist and self.select >= need.select)


def _pow2_cap(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 0 else 0


def caps_for_models(models: Sequence["DensityModel"],
                    round_pow2: bool = True) -> DensityCaps:
    """The smallest :class:`DensityCaps` covering ``models`` (rounded up
    to powers of two by default, so similarly-sized layers share)."""
    coord = div = hist = select = 0
    for m in models:
        # by kind id, not isinstance: this runs for every row of a sweep
        kind = m.kind_id
        if kind == BANDED_ID:
            coord = max(coord, m.rows)
            div = max(div, max(1, math.isqrt(max(1, m.rows * m.cols))))
        elif kind == ACTUAL_ID:
            hist = max(hist, m.tensor_size)
        elif kind == SELECTED_ID:
            select = 1
    if round_pow2:
        coord, div, hist = (_pow2_cap(coord), _pow2_cap(div),
                            _pow2_cap(hist))
    return DensityCaps(coord=coord, div=div, hist=hist, select=select)


# ----------------------------------------------------------------------
# Static traced statistics: <kind>_<stat>_t(params, hist, tile_size).
# ``params`` is the model's NUM_DENSITY_PARAMS vector, ``hist`` its
# (3, H) tile-occupancy histogram (only read by the actual-data kind).
# All are pure jnp closed forms — the single source of truth for both
# the instance ``*_b`` wrappers and the TracedDensityStats switch.
# ----------------------------------------------------------------------
def hyper_empty_t(a, b, t):
    """P(a t-element draw without replacement from ``a`` elements misses
    all ``b`` marked ones): the hypergeometric empty-tile term."""
    import jax.numpy as jnp
    from jax.scipy.special import gammaln
    a, b, t = jnp.broadcast_arrays(a, b, t)
    # all six gammaln terms in one op (see the module docstring)
    g = gammaln(jnp.stack(_log_comb_args(a - b, t) + _log_comb_args(a, t)))
    return jnp.exp(_log_comb_of(a - b, t, g[:3]) - _log_comb_of(a, t, g[3:]))


def _combine_pe(direct, a, b, t, use_hyper, hyper=hyper_empty_t):
    """A ``*_prob_empty_split_t`` tuple -> the probability."""
    import jax.numpy as jnp
    return jnp.where(use_hyper, hyper(a, b, t), direct)


# ``*_prob_empty_split_t(p, h, t)`` -> (direct, a, b, t', use_hyper): the
# statistic is ``hyper_empty_t(a, b, t')`` where ``use_hyper`` holds and
# ``direct`` elsewhere.  Kinds without the term return use_hyper=False
# and inert (1, 0, 0) arguments.
def _no_hyper(direct):
    import jax.numpy as jnp
    one = jnp.ones_like(direct)
    return direct, one, 0.0 * one, 0.0 * one, jnp.zeros_like(direct, bool)


def dense_prob_empty_split_t(p, h, t):
    import jax.numpy as jnp
    del p, h
    return _no_hyper(jnp.zeros_like(t * 1.0))


def dense_prob_empty_t(p, h, t):
    return _combine_pe(*dense_prob_empty_split_t(p, h, t))


def dense_expected_density_t(p, h, t):
    import jax.numpy as jnp
    del p, h
    return jnp.ones_like(t * 1.0)


def dense_max_nnz_t(p, h, t):
    del p, h
    return t * 1.0


def uniform_prob_empty_split_t(p, h, t):
    """params: [tensor_size, nnz, density, -]."""
    import jax.numpy as jnp
    del h
    S, N = p[0], p[1]
    T = jnp.minimum(t * 1.0, S)
    return jnp.zeros_like(T), S, N, T, jnp.ones_like(T, bool)


def uniform_prob_empty_t(p, h, t):
    return _combine_pe(*uniform_prob_empty_split_t(p, h, t))


def uniform_expected_density_t(p, h, t):
    import jax.numpy as jnp
    del h
    return jnp.ones_like(t * 1.0) * p[2]


def uniform_max_nnz_t(p, h, t):
    import jax.numpy as jnp
    del h
    return jnp.minimum(t * 1.0, p[1])


def structured_prob_empty_split_t(p, h, t):
    """params: [tensor_size, n, m, -]."""
    import jax.numpy as jnp
    del h
    n, m = p[1], p[2]
    tt = t * 1.0
    return jnp.zeros_like(tt), m, n, tt, ~(tt >= m - n + 1)


def structured_prob_empty_t(p, h, t):
    return _combine_pe(*structured_prob_empty_split_t(p, h, t))


def structured_expected_density_t(p, h, t):
    import jax.numpy as jnp
    del h
    return jnp.ones_like(t * 1.0) * (p[1] / p[2])


def structured_max_nnz_t(p, h, t):
    import jax.numpy as jnp
    del h
    n, m = p[1], p[2]
    tt = t * 1.0
    full = jnp.floor(tt / m)
    rem = tt - full * m
    return jnp.minimum(tt, full * n + jnp.minimum(rem, n))


def _banded_grid_t(p, t, caps: DensityCaps):
    """Traced mirror of ``BandedModel._tile_shape`` + aligned-grid setup
    with the band geometry as traced params [size, rows, cols, w].

    ``tr`` is the largest divisor of the tile size <= floor(sqrt(t))
    (what the scalar decrement loop finds), found by scanning the static
    divisor range ``1..caps.div``."""
    import jax.numpy as jnp
    rows = jnp.round(p[1]).astype(jnp.int64)
    cols = jnp.round(p[2]).astype(jnp.int64)
    ti = jnp.maximum(1.0, jnp.round(t * 1.0)).astype(jnp.int64)
    d = jnp.arange(1, caps.div + 1, dtype=jnp.int64)
    root = jnp.floor(jnp.sqrt(ti.astype(jnp.float64))).astype(jnp.int64)
    ok = (ti % d == 0) & (d <= root)
    tr = jnp.max(jnp.where(ok, d, 1))
    tc = ti // tr
    nr = jnp.maximum(1, rows // tr)
    nc = jnp.maximum(1, cols // tc)
    return ti, tr, tc, nr, nc, rows, cols


def banded_prob_empty_t(p, h, t, caps: DensityCaps):
    import jax.numpy as jnp
    del h
    _, tr, tc, nr, nc, rows, _cols = _banded_grid_t(p, t, caps)
    w = jnp.round(p[3]).astype(jnp.int64)
    ti = jnp.arange(caps.coord, dtype=jnp.int64)
    r0 = ti * tr
    hh = jnp.minimum(tr, rows - r0)
    # nonempty tiles of row-strip ti: the band's column footprint
    # [r0 - w, r0 + hh - 1 + w] must meet [tj*tc, (tj+1)*tc - 1]
    tj_hi = jnp.minimum(nc - 1, (r0 + hh - 1 + w) // tc)
    tj_lo = jnp.maximum(0, -((-(r0 - w - tc + 1)) // tc))
    nonempty = jnp.clip(tj_hi - tj_lo + 1, 0, nc)
    total = jnp.sum(jnp.where(ti < nr, nonempty, 0))
    return (nr * nc - total) * 1.0 / (nr * nc)


def banded_expected_density_t(p, h, t, caps: DensityCaps):
    import jax.numpy as jnp
    del h
    ti, tr, tc, nr, nc, rows, _cols = _banded_grid_t(p, t, caps)
    w = jnp.round(p[3]).astype(jnp.int64)
    i = jnp.arange(caps.coord, dtype=jnp.int64)
    covered_rows = jnp.minimum(nr * tr, rows)
    covered_cols = nc * tc          # c1 is never clamped to cols
    ln = jnp.clip(jnp.minimum(covered_cols, i + w + 1)
                  - jnp.maximum(0, i - w), 0, None)
    nnz = jnp.sum(jnp.where(i < covered_rows, ln, 0))
    return nnz * 1.0 / ((nr * nc) * 1.0 * ti)


def banded_max_nnz_t(p, h, t, caps: DensityCaps):
    import jax
    import jax.numpy as jnp
    del h
    ti, tr, tc, nr, _nc, rows, cols = _banded_grid_t(p, t, caps)
    w = jnp.round(p[3]).astype(jnp.int64)
    i = jnp.arange(caps.coord, dtype=jnp.int64)
    tix = i // tr
    r0 = tix * tr
    # the densest aligned tile sits on the diagonal: slide each
    # row-strip's column window to hug the band
    c0 = jnp.clip(r0 - w, 0, jnp.maximum(0, cols - tc))
    ln = jnp.clip(jnp.minimum(c0 + tc, i + w + 1)
                  - jnp.maximum(c0, i - w), 0, None)
    ln = jnp.where(i < jnp.minimum(nr * tr, rows), ln, 0)
    per_tile = jax.ops.segment_sum(ln, tix, num_segments=caps.coord)
    best = jnp.max(per_tile)
    root = jnp.floor(jnp.sqrt(ti.astype(jnp.float64))).astype(jnp.int64)
    fallback = jnp.minimum(ti, (2 * w + 1) * root + 1)
    return jnp.where(best > 0, jnp.minimum(ti, best), fallback) * 1.0


#: a tile that spans at most this many fibres of a selection takes its
#: empty probability as the exact product prod_j (1 - k / (L - j)); the
#: hypergeometric term's log-gamma differences of L-sized arguments lose
#: ~1e-9 of it, which 1 - P(empty) (about k / L) magnifies
SELECT_PRODUCT = 64


def _selected_fibres_t(p, t):
    """Fibres a tile of ``t`` elements spans: ceil(t / fibre), at most L."""
    import jax.numpy as jnp
    return jnp.minimum(jnp.ceil(t * 1.0 / p[3]), p[1])


def selected_prob_empty_split_t(p, h, t):
    """params: [tensor_size, L, k, fibre]."""
    import jax.numpy as jnp
    del h
    L, k = p[1], p[2]
    c = _selected_fibres_t(p, t)
    j = jnp.arange(SELECT_PRODUCT, dtype=c.dtype)
    # j < c <= L - k keeps k / (L - j) < 1; past L - k the product is 0
    x = jnp.minimum(k / jnp.maximum(L - j, 1.0), 1.0)
    logs = jnp.where(j < c[..., None], jnp.log1p(-x), 0.0)
    direct = jnp.exp(jnp.sum(logs, axis=-1))
    return direct, L * jnp.ones_like(c), k * jnp.ones_like(c), c, \
        c > SELECT_PRODUCT


def selected_prob_empty_t(p, h, t):
    return _combine_pe(*selected_prob_empty_split_t(p, h, t))


def selected_expected_density_t(p, h, t):
    import jax.numpy as jnp
    del h
    return jnp.ones_like(t * 1.0) * (p[2] / p[1])


def selected_max_nnz_t(p, h, t):
    import jax.numpy as jnp
    del h
    return jnp.minimum(t * 1.0, p[2] * p[3])


def _actual_index(p, t):
    """Histogram row for a (clamped) traced tile size; params[0] is the
    valid table length (the concrete array's size)."""
    import jax.numpy as jnp
    n = jnp.round(p[0]).astype(jnp.int64)
    tt = jnp.round(t * 1.0).astype(jnp.int64)
    return jnp.clip(jnp.minimum(tt, n), 1, None) - 1


def actual_prob_empty_t(p, h, t):
    return h[0, _actual_index(p, t)]


def _split_of(prob_empty_t):
    """Split form of a kind whose prob_empty has no hypergeometric term."""
    return lambda p, h, t: _no_hyper(prob_empty_t(p, h, t) * 1.0)


def actual_expected_density_t(p, h, t):
    return h[1, _actual_index(p, t)]


def actual_max_nnz_t(p, h, t):
    return h[2, _actual_index(p, t)]


class HyperBatch:
    """Evaluates every hypergeometric term of one traced function in a
    single ``gammaln``/``exp`` op, by tracing the function twice:

        HyperBatch.run(lambda hyper: body(..., hyper))

    The first trace records each term's arguments and yields
    placeholders; the terms are then evaluated together, and the second
    trace replays their values in call order.  The first trace's outputs
    are unused, so the compiler drops them.  The result is the same,
    element by element, as evaluating each term where it is called; the
    tile sizes a term is asked about never depend on an earlier term."""

    def __init__(self):
        self._args: list = []
        self._vals = None

    def __call__(self, a, b, t):
        import jax.numpy as jnp
        a, b, t = jnp.broadcast_arrays(a, b, t)
        if self._vals is None:
            self._args.append((a, b, t))
            return jnp.zeros(a.shape, a.dtype)
        return next(self._vals)

    def _solve(self) -> None:
        import jax.numpy as jnp
        if not self._args:
            self._vals = iter(())
            return
        flat = [jnp.concatenate([jnp.ravel(x[i]) for x in self._args])
                for i in range(3)]
        vals = hyper_empty_t(*flat)
        out, off = [], 0
        for a, _, _ in self._args:
            out.append(vals[off:off + a.size].reshape(a.shape))
            off += a.size
        self._vals = iter(out)

    @classmethod
    def run(cls, fn):
        hyper = cls()
        fn(hyper)
        hyper._solve()
        return fn(hyper)


class TracedDensityStats:
    """Per-kind traced tile statistics behind one runtime model-id
    switch: ``prob_empty(kind, params, hist, tile_size)`` (and
    ``expected_density`` / ``max_nnz``) dispatch on the *traced* kind id
    with ``lax.switch``, so one compiled program evaluates tensors of
    mixed density kinds and the kind itself is workload data.  Branches
    whose static capacity is zero (no banded / no actual tensor can ever
    be selected) are pruned to the trivial dense form so pure-statistical
    programs pay nothing for them.  ``prob_empty`` switches on the split
    forms and evaluates the hypergeometric term after the switch, through
    ``hyper`` (a :class:`HyperBatch` inside the engine's program)."""

    def __init__(self, caps: DensityCaps):
        self.caps = caps
        banded_ok = caps.coord > 0 and caps.div > 0
        actual_ok = caps.hist > 0

        def with_caps(fn):
            return lambda p, h, t: fn(p, h, t, caps)

        self._pe = (dense_prob_empty_split_t, uniform_prob_empty_split_t,
                    structured_prob_empty_split_t,
                    _split_of(with_caps(banded_prob_empty_t)) if banded_ok
                    else dense_prob_empty_split_t,
                    _split_of(actual_prob_empty_t) if actual_ok
                    else dense_prob_empty_split_t)
        self._ed = (dense_expected_density_t, uniform_expected_density_t,
                    structured_expected_density_t,
                    with_caps(banded_expected_density_t) if banded_ok
                    else dense_expected_density_t,
                    actual_expected_density_t if actual_ok
                    else dense_expected_density_t)
        self._mx = (dense_max_nnz_t, uniform_max_nnz_t,
                    structured_max_nnz_t,
                    with_caps(banded_max_nnz_t) if banded_ok
                    else dense_max_nnz_t,
                    actual_max_nnz_t if actual_ok else dense_max_nnz_t)
        if caps.select:
            # no selected tensor: no branch at all, so such programs are
            # the ones they were before the kind existed
            self._pe += (selected_prob_empty_split_t,)
            self._ed += (selected_expected_density_t,)
            self._mx += (selected_max_nnz_t,)

    @staticmethod
    def _switch(branches, kind, params, hist, tile_size):
        import jax
        import jax.numpy as jnp
        return jax.lax.switch(jnp.asarray(kind, jnp.int32), list(branches),
                              params, hist, tile_size * 1.0)

    def prob_empty(self, kind, params, hist, tile_size,
                   hyper=hyper_empty_t):
        return _combine_pe(*self._switch(self._pe, kind, params, hist,
                                         tile_size), hyper=hyper)

    def expected_density(self, kind, params, hist, tile_size):
        return self._switch(self._ed, kind, params, hist, tile_size)

    def max_nnz(self, kind, params, hist, tile_size):
        return self._switch(self._mx, kind, params, hist, tile_size)


class DensityModel:
    """Base interface; tile_size is the flattened number of elements."""

    #: True when the *_b methods below are traceable closed forms usable
    #: from vmapped/jitted code (core.batched).  Every Table-4 model now
    #: is (actual-data via its tile-occupancy histogram).
    batched: bool = False

    #: index into MODEL_KINDS / the TracedDensityStats switch
    kind_id: int = DENSE_ID

    def params(self) -> np.ndarray:
        """Fixed-shape traced parameter vector (NUM_DENSITY_PARAMS,).

        The traced ``<kind>_<stat>_t`` forms consume this, so a compiled
        program can evaluate a *different* instance of the same kind by
        swapping the vector — model parameters are workload data."""
        return np.zeros(NUM_DENSITY_PARAMS)

    def hist_table(self) -> np.ndarray:
        """(3, n) tile-occupancy histogram; only actual-data models have
        a non-empty one."""
        return np.zeros((3, 0))

    def prob_empty_b(self, tile_size):
        """Traceable ``prob_empty``: tile_size is a jnp scalar/array."""
        raise BatchedDensityUnsupported(type(self).__name__)

    def prob_nonempty_b(self, tile_size):
        return 1.0 - self.prob_empty_b(tile_size)

    def expected_density_b(self, tile_size):
        raise BatchedDensityUnsupported(type(self).__name__)

    def max_nnz_b(self, tile_size):
        raise BatchedDensityUnsupported(type(self).__name__)

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

    def expected_density_nonempty(self, tile_size: int) -> float:
        """E[density | tile nonempty] — used for fibers of nonempty parents."""
        pne = self.prob_nonempty(tile_size)
        if pne <= 0.0:
            return 0.0
        return min(1.0, self.expected_density(tile_size) / pne)


@dataclasses.dataclass
class DenseModel(DensityModel):
    tensor_size: int = 1
    density: float = 1.0
    batched = True
    kind_id = DENSE_ID

    def prob_empty(self, tile_size: int) -> float:
        return 0.0

    def max_nnz(self, tile_size: int) -> int:
        return tile_size

    def prob_empty_b(self, tile_size):
        return dense_prob_empty_t(None, None, tile_size)

    def expected_density_b(self, tile_size):
        return dense_expected_density_t(None, None, tile_size)

    def max_nnz_b(self, tile_size):
        return dense_max_nnz_t(None, None, tile_size)


@dataclasses.dataclass
class UniformModel(DensityModel):
    """nnz locations uniformly random: tile nnz ~ Hypergeometric(S, N, T)."""

    tensor_size: int
    density: float
    batched = True

    @property
    def nnz(self) -> int:
        return round(self.density * self.tensor_size)

    def prob_empty(self, tile_size: int) -> float:
        S, N, T = self.tensor_size, self.nnz, min(tile_size, self.tensor_size)
        # P(empty) = C(S-N, T) / C(S, T)
        lp = _log_comb(S - N, T) - _log_comb(S, T)
        return math.exp(lp) if lp > -700 else 0.0

    def prob_nnz_eq(self, tile_size: int, k: int) -> float:
        S, N, T = self.tensor_size, self.nnz, min(tile_size, self.tensor_size)
        lp = (_log_comb(N, k) + _log_comb(S - N, T - k) - _log_comb(S, T))
        return math.exp(lp) if lp > -700 else 0.0

    def max_nnz(self, tile_size: int) -> int:
        return min(tile_size, self.nnz)

    kind_id = UNIFORM_ID

    def params(self) -> np.ndarray:
        return np.asarray([self.tensor_size, self.nnz, self.density, 0.0])

    def prob_empty_b(self, tile_size):
        return uniform_prob_empty_t(self.params(), None, tile_size)

    def expected_density_b(self, tile_size):
        return uniform_expected_density_t(self.params(), None, tile_size)

    def max_nnz_b(self, tile_size):
        return uniform_max_nnz_t(self.params(), None, tile_size)


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

    batched = True
    kind_id = STRUCTURED_ID

    def params(self) -> np.ndarray:
        return np.asarray([self.tensor_size, self.n, self.m, 0.0],
                          np.float64)

    def prob_empty_b(self, tile_size):
        return structured_prob_empty_t(self.params(), None, tile_size)

    def expected_density_b(self, tile_size):
        return structured_expected_density_t(self.params(), None,
                                             tile_size)

    def max_nnz_b(self, tile_size):
        return structured_max_nnz_t(self.params(), None, tile_size)


@dataclasses.dataclass
class BandedModel(DensityModel):
    """Diagonally banded 2-D tensor: A[i,j] != 0 iff |i - j| <= half_band.

    Coordinate-dependent: tiles on the diagonal are dense-ish, off-diagonal
    tiles are empty.  Tile statistics are derived analytically by counting
    band overlap over all aligned tile positions.

    The ``*_b`` methods are traceable closed forms of the same counts: a
    tile is nonempty iff the band's column footprint over the tile's rows,
    ``[r0 - w, r0 + h - 1 + w]``, intersects the tile's column interval —
    so the nonempty tiles of one row-strip form a contiguous ``tj`` range
    computable with two integer divisions; expected density reduces to
    the band population of the covered rectangle (one O(rows) masked
    reduction).  This keeps banded workloads on the batched JAX engine;
    only ``actual``-data models remain scalar-only.
    """

    rows: int
    cols: int
    half_band: int
    batched = True

    @property
    def tensor_size(self) -> int:  # type: ignore[override]
        return self.rows * self.cols

    @property
    def density(self) -> float:  # type: ignore[override]
        nnz = sum(
            min(self.cols, i + self.half_band + 1) - max(0, i - self.half_band)
            for i in range(self.rows)
        )
        return nnz / self.tensor_size

    def _tile_shape(self, tile_size: int) -> tuple[int, int]:
        """Assume square-ish tiles unless told otherwise (see tile_stats)."""
        tr = int(math.sqrt(tile_size))
        while tile_size % tr:
            tr -= 1
        return tr, tile_size // tr

    def tile_stats(self, tile_rows: int, tile_cols: int) -> tuple[float, float]:
        """(P(tile empty), E[tile density]) over aligned tile positions."""
        nr = max(1, self.rows // max(1, tile_rows))
        nc = max(1, self.cols // max(1, tile_cols))
        empty = 0
        dens = 0.0
        for ti in range(nr):
            r0, r1 = ti * tile_rows, (ti + 1) * tile_rows
            for tj in range(nc):
                c0, c1 = tj * tile_cols, (tj + 1) * tile_cols
                nnz = 0
                for i in range(r0, min(r1, self.rows)):
                    lo = max(c0, i - self.half_band)
                    hi = min(c1, i + self.half_band + 1)
                    nnz += max(0, hi - lo)
                if nnz == 0:
                    empty += 1
                dens += nnz / (tile_rows * tile_cols)
        total = nr * nc
        return empty / total, dens / total

    def prob_empty(self, tile_size: int) -> float:
        return self.tile_stats(*self._tile_shape(tile_size))[0]

    def expected_density(self, tile_size: int) -> float:
        return self.tile_stats(*self._tile_shape(tile_size))[1]

    def max_nnz(self, tile_size: int) -> int:
        tr, tc = self._tile_shape(tile_size)
        # densest tile sits on the diagonal
        best = 0
        for ti in range(max(1, self.rows // max(1, tr))):
            r0 = ti * tr
            c0 = min(max(0, r0 - self.half_band), max(0, self.cols - tc))
            nnz = 0
            for i in range(r0, min(r0 + tr, self.rows)):
                lo = max(c0, i - self.half_band)
                hi = min(c0 + tc, i + self.half_band + 1)
                nnz += max(0, hi - lo)
            best = max(best, nnz)
        return min(tile_size, best if best else self.max_band_nnz(tile_size))

    def max_band_nnz(self, tile_size: int) -> int:
        return min(tile_size, (2 * self.half_band + 1) * int(math.sqrt(tile_size)) + 1)

    # ---------------- traceable closed forms (core.batched) ----------------
    kind_id = BANDED_ID

    def params(self) -> np.ndarray:
        return np.asarray([self.tensor_size, self.rows, self.cols,
                           self.half_band], np.float64)

    def _self_caps(self) -> DensityCaps:
        """Exact (unrounded) capacities for the instance wrappers."""
        return DensityCaps(
            coord=self.rows,
            div=max(1, math.isqrt(max(1, self.rows * self.cols))))

    def prob_empty_b(self, tile_size):
        return banded_prob_empty_t(self.params(), None, tile_size,
                                   self._self_caps())

    def expected_density_b(self, tile_size):
        return banded_expected_density_t(self.params(), None, tile_size,
                                         self._self_caps())

    def max_nnz_b(self, tile_size):
        return banded_max_nnz_t(self.params(), None, tile_size,
                                self._self_caps())


#: tile-occupancy histograms keyed by the identity of the source array:
#: the table costs O(n log n) to build (and the workload's density spec
#: holds the same ndarray across model rebuilds), so it is computed once
#: per concrete array.  Entries keep the array alive so ids stay valid.
_HIST_CACHE: dict[int, tuple[object, np.ndarray]] = {}
_HIST_CACHE_CAP = 32


@dataclasses.dataclass
class ActualDataModel(DensityModel):
    """Exact empirical statistics from a concrete numpy array.

    This is the paper's "actual data" model: slower but exact, used e.g. for
    the Eyeriss-V2 validation where statistical approximation is the main
    error source (Sec. 6.3.2).

    The traced path lowers the array to a device-resident *tile-occupancy
    histogram* (:meth:`hist_table`): exact per-tile-size statistics
    precomputed once, gathered by traced tile size — so actual-data
    workloads ride the batched/bucketed JAX engine like every other
    density kind.
    """

    data: np.ndarray

    def __post_init__(self) -> None:
        self._flat_nz = (np.asarray(self.data) != 0)
        self._hist: np.ndarray | None = None

    @property
    def tensor_size(self) -> int:  # type: ignore[override]
        return int(self._flat_nz.size)

    @property
    def density(self) -> float:  # type: ignore[override]
        return float(self._flat_nz.mean()) if self._flat_nz.size else 0.0

    def _tiled_nnz(self, tile_size: int) -> np.ndarray:
        """nnz per aligned 1-D tile of the flattened tensor.

        For multi-dim tile shapes callers should use :meth:`tile_nnz_grid`.
        """
        flat = self._flat_nz.reshape(-1)
        n = (flat.size // tile_size) * tile_size
        if n == 0:
            return np.array([flat.sum()])
        return flat[:n].reshape(-1, tile_size).sum(axis=1)

    def tile_nnz_grid(self, tile_dims: Sequence[int]) -> np.ndarray:
        """Exact nnz of every aligned tile of shape tile_dims."""
        a = self._flat_nz
        if a.ndim != len(tile_dims):
            return self._tiled_nnz(int(np.prod(tile_dims)))
        slices, new_shape = [], []
        for ext, t in zip(a.shape, tile_dims):
            t = min(t, ext)
            n = (ext // t) * t
            slices.append(slice(0, n))
            new_shape += [ext // t, t]
        a = a[tuple(slices)].reshape(new_shape)
        # sum over the intra-tile axes (odd positions)
        return a.sum(axis=tuple(range(1, 2 * len(tile_dims), 2)))

    def prob_empty(self, tile_size: int) -> float:
        nnz = self._tiled_nnz(min(tile_size, self.tensor_size))
        return float((nnz == 0).mean())

    def expected_density(self, tile_size: int) -> float:
        t = min(tile_size, self.tensor_size)
        return float(self._tiled_nnz(t).mean() / t)

    def max_nnz(self, tile_size: int) -> int:
        return int(self._tiled_nnz(min(tile_size, self.tensor_size)).max())

    # ------------- tile-occupancy histogram (traced lowering) -------------
    batched = True
    kind_id = ACTUAL_ID

    def params(self) -> np.ndarray:
        return np.asarray([self.tensor_size, self.density, 0.0, 0.0],
                          np.float64)

    def hist_table(self) -> np.ndarray:
        """(3, tensor_size) exact per-tile-size statistics: row 0 is
        ``prob_empty``, row 1 ``expected_density``, row 2 ``max_nnz``
        for every aligned 1-D tile size ``t = 1..tensor_size`` of the
        flattened array — the same semantics as the scalar methods above
        (non-divisible tails dropped, the remainder-free prefix tiled).
        Built from one cumulative sum, vectorized over divisor blocks
        (all tile sizes sharing a tile *count* ``m = n // t`` are one
        numpy gather): O(n log n) element work in O(sqrt n) Python
        iterations.  Cached per source array."""
        if self._hist is not None:
            return self._hist
        key = id(self.data)
        cached = _HIST_CACHE.get(key)
        if cached is not None and cached[0] is self.data:
            self._hist = cached[1]
            return self._hist
        flat = self._flat_nz.reshape(-1).astype(np.int64)
        n = flat.size
        out = np.zeros((3, n))
        cs = np.concatenate([[0], np.cumsum(flat)])
        t = 1
        while t <= n:
            m = n // t                     # aligned tiles at this size
            t_hi = n // m                  # all t in [t, t_hi] share m
            ts = np.arange(t, t_hi + 1)
            edges = ts[None, :] * np.arange(m + 1)[:, None]
            tiles = np.diff(cs[edges], axis=0)          # (m, len(ts))
            out[0, ts - 1] = (tiles == 0).mean(axis=0)
            out[1, ts - 1] = tiles.mean(axis=0) / ts
            out[2, ts - 1] = tiles.max(axis=0)
            t = t_hi + 1
        self._hist = out
        if len(_HIST_CACHE) >= _HIST_CACHE_CAP:
            _HIST_CACHE.pop(next(iter(_HIST_CACHE)))
        _HIST_CACHE[key] = (self.data, out)
        return out

    def _hist_b(self):
        import jax.numpy as jnp
        return jnp.asarray(self.hist_table())

    def prob_empty_b(self, tile_size):
        return actual_prob_empty_t(self.params(), self._hist_b(),
                                   tile_size)

    def expected_density_b(self, tile_size):
        return actual_expected_density_t(self.params(), self._hist_b(),
                                         tile_size)

    def max_nnz_b(self, tile_size):
        return actual_max_nnz_t(self.params(), self._hist_b(), tile_size)


@dataclasses.dataclass
class SelectedModel(DensityModel):
    """A top-k selection: of ``L`` fibres, each ``fibre`` elements long
    along the tensor's dense rank, exactly ``k`` hold data, at positions
    drawn uniformly and shared by the whole fibre (aligned across the
    dense rank).  A tile of t elements spans c = ceil(t / fibre) fibres,
    so

      P(empty)  = C(L - c, k) / C(L, k)   (uniform's hypergeometric, over
                                          L fibres instead of elements)
      E[density] = k / L,   max_nnz = min(t, k * fibre).

    This is exact when a tile holds whole fibres, its extent along the
    dense rank the rank's whole extent: the fleet sweep's ``tpu_mapping``
    gives that for both DeepSeek Sparse Attention products, whose
    selected operand is the latent cache, since its fibre fits one VMEM
    tile — a 576-long column in the score product (K <= bk), a 512-long
    row in the value product (N <= bn).  Narrower tiles are
    undercounted.  A spec's ``rank`` (which rank is selected) is the
    design's concern, not the model's."""

    tensor_size: int
    L: int
    k: int
    fibre: int
    batched = True
    kind_id = SELECTED_ID

    def __post_init__(self) -> None:
        if not 0 < self.k <= self.L or self.L * self.fibre != \
                self.tensor_size:
            raise ValueError(
                f"a selection of {self.k} of {self.L} fibres of "
                f"{self.fibre} does not fit a tensor of {self.tensor_size}")

    @property
    def density(self) -> float:  # type: ignore[override]
        return self.k / self.L

    def fibres(self, tile_size: int) -> int:
        return min(math.ceil(tile_size / self.fibre), self.L)

    def prob_empty(self, tile_size: int) -> float:
        L, k, c = self.L, self.k, self.fibres(tile_size)
        if c > L - k:
            return 0.0
        if c <= SELECT_PRODUCT:
            return math.exp(sum(math.log1p(-k / (L - j)) for j in range(c)))
        # C(L-c, k) / C(L, k) written as the hypergeometric term's
        # C(L-k, c) / C(L, c), as the traced form evaluates it
        lp = _log_comb(L - k, c) - _log_comb(L, c)
        return math.exp(lp) if lp > -700 else 0.0

    def max_nnz(self, tile_size: int) -> int:
        return min(tile_size, self.k * self.fibre)

    def params(self) -> np.ndarray:
        return np.asarray([self.tensor_size, self.L, self.k, self.fibre],
                          np.float64)

    def prob_empty_b(self, tile_size):
        return selected_prob_empty_t(self.params(), None, tile_size)

    def expected_density_b(self, tile_size):
        return selected_expected_density_t(self.params(), None, tile_size)

    def max_nnz_b(self, tile_size):
        return selected_max_nnz_t(self.params(), None, tile_size)


def make_density_model(spec: object, tensor_size: int) -> DensityModel:
    """Build a model from a workload density spec tuple."""
    if spec is None:
        return DenseModel(tensor_size)
    kind, arg = spec  # type: ignore[misc]
    if kind == "dense":
        return DenseModel(tensor_size)
    if kind == "uniform":
        return UniformModel(tensor_size=tensor_size, density=float(arg))
    if kind == "structured":
        return StructuredModel(tensor_size=tensor_size,
                               n=int(arg["n"]), m=int(arg["m"]))
    if kind == "banded":
        return BandedModel(rows=int(arg["rows"]), cols=int(arg["cols"]),
                           half_band=int(arg["half_band"]))
    if kind == "actual":
        return ActualDataModel(data=np.asarray(arg))
    if kind == "selected":
        return SelectedModel(tensor_size=tensor_size, L=int(arg["L"]),
                             k=int(arg["k"]), fibre=int(arg["fibre"]))
    raise ValueError(f"unknown density spec {spec!r}")
