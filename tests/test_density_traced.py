"""Traced parametric density interface (workload-as-data, ISSUE 4).

Pins the contract that lets one compiled program serve a whole network:
every density model lowers to a fixed-shape parameter vector + kind id
(plus, for actual-data, a tile-occupancy histogram), and the static
``*_t`` traced forms behind the ``TracedDensityStats`` model-id switch
reproduce the scalar oracle methods to <= 1e-6 — across kinds, at
non-divisible tile sizes, and on all-zero tiles."""
import numpy as np
import pytest

from repro.core.density import (ActualDataModel, BandedModel, DenseModel,
                                DensityCaps, StructuredModel,
                                TracedDensityStats, UniformModel,
                                caps_for_models)


def _stats_for(models):
    return TracedDensityStats(caps_for_models(models))


def _check_parity(model, stats, tile_sizes, rel=1e-6):
    """Traced switch-dispatched stats == scalar oracle methods."""
    import jax.numpy as jnp
    from jax import enable_x64
    with enable_x64():
        params = jnp.asarray(model.params())
        hist = np.zeros((3, stats.caps.hist))
        table = model.hist_table()
        hist[:, : table.shape[1]] = table
        hist = jnp.asarray(hist)
        kind = model.kind_id
        for t in tile_sizes:
            pe = float(stats.prob_empty(kind, params, hist, float(t)))
            ed = float(stats.expected_density(kind, params, hist,
                                              float(t)))
            mx = float(stats.max_nnz(kind, params, hist, float(t)))
            assert pe == pytest.approx(model.prob_empty(t), abs=rel), \
                (type(model).__name__, t)
            assert ed == pytest.approx(model.expected_density(t),
                                       rel=rel, abs=rel), \
                (type(model).__name__, t)
            assert mx == pytest.approx(model.max_nnz(t), rel=rel), \
                (type(model).__name__, t)


# ----------------------------------------------------------------------
# actual-data: the tile-occupancy histogram lowering
# ----------------------------------------------------------------------
def test_actual_histogram_matches_scalar_oracle():
    """Every tile size of a ragged (non-power-of-two) array, including
    non-divisible ones, reproduces the scalar ActualDataModel exactly."""
    rng = np.random.default_rng(0)
    a = (rng.random((7, 13)) < 0.3).astype(float)      # 91 elements
    m = ActualDataModel(data=a)
    stats = _stats_for([m])
    # every tile size + past-the-end clamping (t > tensor_size)
    _check_parity(m, stats, list(range(1, 92)) + [100, 1000])


def test_actual_histogram_all_zero_and_dense_rows():
    """All-zero arrays (every tile empty) and a single dense row (the
    Fig. 9 coordinate-dependence case) both survive the lowering."""
    zero = ActualDataModel(data=np.zeros((6, 6)))
    assert zero.density == 0.0
    _check_parity(zero, _stats_for([zero]), [1, 2, 5, 7, 36, 50])

    a = np.zeros((8, 8))
    a[0, :] = 1.0
    row = ActualDataModel(data=a)
    stats = _stats_for([row])
    _check_parity(row, stats, [1, 3, 8, 9, 64])
    # spot-check the documented scalar facts through the traced path
    import jax.numpy as jnp
    from jax import enable_x64
    with enable_x64():
        hist = np.zeros((3, stats.caps.hist))
        hist[:, :64] = row.hist_table()
        pe = float(stats.prob_empty(row.kind_id,
                                    jnp.asarray(row.params()),
                                    jnp.asarray(hist), 8.0))
        assert pe == pytest.approx(7 / 8)


def test_actual_histogram_table_semantics():
    """Row meanings: [prob_empty, expected_density, max_nnz] per aligned
    1-D tile size, non-divisible tails dropped like the scalar path."""
    a = np.asarray([1.0, 0.0, 0.0, 1.0, 1.0])   # n=5
    m = ActualDataModel(data=a)
    table = m.hist_table()
    assert table.shape == (3, 5)
    # t=2 -> tiles [1,0], [0,1] (tail element dropped): none empty
    assert table[0, 1] == 0.0
    assert table[1, 1] == pytest.approx(0.5)
    assert table[2, 1] == 1.0
    # t=3 -> single tile [1,0,0]: nonempty, density 1/3, max 1
    assert table[0, 2] == 0.0
    assert table[1, 2] == pytest.approx(1 / 3)
    assert table[2, 2] == 1.0


def test_actual_batched_wrappers_traceable_under_vmap():
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    rng = np.random.default_rng(3)
    m = ActualDataModel(data=(rng.random(48) < 0.4).astype(float))
    assert m.batched
    with enable_x64():
        tiles = jnp.asarray([1.0, 3.0, 7.0, 16.0, 48.0])
        pe = jax.jit(jax.vmap(m.prob_empty_b))(tiles)
        mx = jax.jit(jax.vmap(m.max_nnz_b))(tiles)
        for t, a_, b_ in zip(tiles, pe, mx):
            assert float(a_) == pytest.approx(m.prob_empty(int(t)))
            assert float(b_) == float(m.max_nnz(int(t)))


# ----------------------------------------------------------------------
# statistical kinds through the same switch
# ----------------------------------------------------------------------
def test_traced_stats_parity_all_statistical_kinds():
    models = [
        DenseModel(tensor_size=64),
        UniformModel(tensor_size=256, density=0.3),   # nnz rounding != d
        StructuredModel(tensor_size=128, n=2, m=4),
        BandedModel(rows=16, cols=24, half_band=2),
    ]
    stats = _stats_for(models)
    for m in models:
        _check_parity(m, stats, [1, 2, 3, 4, 6, 8, 16, 25, 64],
                      rel=1e-9)


def test_caps_cover_and_pow2_rounding():
    banded = BandedModel(rows=48, cols=48, half_band=3)
    actual = ActualDataModel(data=np.ones(100))
    caps = caps_for_models([banded, actual])
    assert caps.coord >= 48 and caps.hist >= 100 and caps.div >= 48
    # powers of two, so similarly-sized layers share a program
    for v in (caps.coord, caps.div, caps.hist):
        assert v & (v - 1) == 0
    assert caps.covers(caps_for_models([banded]))
    assert not DensityCaps().covers(caps)
    merged = DensityCaps(coord=4).merge(DensityCaps(hist=8))
    assert merged == DensityCaps(coord=4, div=0, hist=8)
    # uniform-only workloads need no padding at all -> shared everywhere
    assert caps_for_models([UniformModel(1024, 0.5)]) == DensityCaps()


# ----------------------------------------------------------------------
# HyperBatch: every hypergeometric term of a program in one gammaln op
# ----------------------------------------------------------------------
def _hyper_program(models, tiles):
    """A traced function asking prob_empty of each (model, tile) pair,
    with the hypergeometric terms evaluated through ``hyper``."""
    import jax.numpy as jnp
    stats = _stats_for(models)
    hist = jnp.zeros((3, max(1, stats.caps.hist)))

    def body(ts, hyper):
        return jnp.stack([
            stats.prob_empty(m.kind_id, jnp.asarray(m.params()), hist,
                             t, hyper)
            for m in models for t in ts])
    return body, jnp.asarray(tiles, jnp.float64)


@pytest.mark.parametrize("batched", [False, True])
def test_hyper_batch_is_bit_identical_to_per_call_terms(batched):
    """The two-trace batch gives, element by element, the bits of the
    terms evaluated where they are asked for (and matches the oracle)."""
    import jax
    from jax import enable_x64

    from repro.core.density import HyperBatch, hyper_empty_t
    models = [UniformModel(tensor_size=4096, density=0.3),
              StructuredModel(tensor_size=4096, n=2, m=8), DenseModel(64)]
    tiles = [1.0, 2.0, 3.0, 7.0, 8.0, 64.0, 500.0]
    with enable_x64():
        body, ts = _hyper_program(models, tiles)
        direct = jax.jit(lambda t: body(t, hyper_empty_t))(ts)
        if batched:
            got = jax.jit(lambda t: HyperBatch.run(
                lambda h: body(t, h)))(ts)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(direct))
    want = [m.prob_empty(int(t)) for m in models for t in tiles]
    np.testing.assert_allclose(np.asarray(direct), want, atol=1e-12)


def test_hyper_batch_lowers_to_one_gammaln():
    import jax
    from jax import enable_x64

    from repro.core.density import HyperBatch
    models = [UniformModel(tensor_size=4096, density=0.3),
              StructuredModel(tensor_size=4096, n=2, m=4)]
    with enable_x64():
        body, ts = _hyper_program(models, [1.0, 4.0, 9.0])
        text = jax.jit(lambda t: HyperBatch.run(
            lambda h: body(t, h))).lower(ts).as_text()
    assert text.count("chlo.lgamma") == 1
