"""Density model unit + property tests (paper Sec. 5.3.2, Table 4, Fig. 9)."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.density import (ActualDataModel, BandedModel, DenseModel,
                                StructuredModel, UniformModel,
                                make_density_model)


def test_uniform_matches_monte_carlo():
    S, d, T = 1024, 0.25, 16
    m = UniformModel(tensor_size=S, density=d)
    rng = np.random.default_rng(0)
    trials = 3000
    empties = 0
    for _ in range(trials):
        idx = rng.choice(S, size=m.nnz, replace=False)
        a = np.zeros(S)
        a[idx] = 1
        if a[:T].sum() == 0:
            empties += 1
    assert abs(m.prob_empty(T) - empties / trials) < 0.03
    assert abs(m.expected_density(T) - d) < 1e-12


def test_uniform_fig9_shape_dependence():
    """Fig. 9: smaller tiles have higher empty probability."""
    m = UniformModel(tensor_size=4096, density=0.5)
    probs = [m.prob_empty(t) for t in (1, 2, 4, 8, 16)]
    assert all(a > b for a, b in zip(probs, probs[1:]))
    assert abs(m.prob_empty(1) - 0.5) < 1e-9


def test_structured_deterministic_at_block():
    m = StructuredModel(tensor_size=1024, n=2, m=4)
    assert m.expected_density(128) == 0.5
    assert m.prob_empty(4) == 0.0       # every block holds exactly 2 nnz
    assert m.prob_empty(8) == 0.0
    assert m.max_nnz(8) == 4            # exactly n per block
    assert m.max_nnz(6) == 4            # 1 full block + partial capped at n
    # sub-block tiles can be empty: 1 element empty w.p. 1 - 2/4
    assert abs(m.prob_empty(1) - 0.5) < 1e-9


def test_banded_coordinate_dependence():
    m = BandedModel(rows=64, cols=64, half_band=2)
    p_empty, dens = m.tile_stats(8, 8)
    # most tiles are off-diagonal and empty (8x8 grid: diagonal + adjacent
    # sub-diagonal tiles are nonempty -> 22/64 nonempty)
    assert p_empty > 0.6
    assert 0 < dens < 0.2
    assert 0 < m.density < 0.2


def test_actual_data_exact():
    a = np.zeros((8, 8))
    a[0, :] = 1.0          # one dense row
    m = ActualDataModel(data=a)
    assert m.density == pytest.approx(1 / 8)
    # aligned 8-element (row) tiles: exactly 1 of 8 nonempty
    assert m.prob_empty(8) == pytest.approx(7 / 8)
    assert m.max_nnz(8) == 8


@given(st.integers(16, 512), st.floats(0.01, 0.99), st.integers(1, 64))
@settings(max_examples=60, deadline=None)
def test_uniform_properties(S, d, T):
    T = min(T, S)
    m = UniformModel(tensor_size=S, density=d)
    p = m.prob_empty(T)
    assert 0.0 <= p <= 1.0
    # P(empty) <= (1 - density of one element)
    assert p <= m.prob_empty(1) + 1e-9
    # expectations within bounds
    assert 0.0 <= m.expected_nnz(T) <= T + 1e-9
    assert m.max_nnz(T) >= math.floor(m.expected_nnz(T)) - 1


@given(st.integers(2, 8))
@settings(max_examples=20, deadline=None)
def test_structured_prob_empty_monotone(m_block):
    m = StructuredModel(tensor_size=64 * m_block, n=1, m=m_block)
    probs = [m.prob_empty(t) for t in range(1, m_block + 1)]
    assert all(a >= b - 1e-12 for a, b in zip(probs, probs[1:]))
    assert probs[-1] == 0.0 or m_block == 1


def test_banded_batched_closed_forms_match_scalar():
    """The traceable banded expressions reproduce the scalar grid-count
    loops exactly (prob_empty / expected_density / max_nnz)."""
    from jax import enable_x64
    with enable_x64():
        for (rows, cols, w) in [(64, 64, 2), (64, 48, 5), (37, 53, 3),
                                (16, 16, 0), (8, 64, 7)]:
            m = BandedModel(rows=rows, cols=cols, half_band=w)
            for t in (1, 2, 3, 4, 6, 8, 16, 25, 30, 64, 100, 255, 256,
                      512, rows * cols):
                assert float(m.prob_empty_b(float(t))) == pytest.approx(
                    m.prob_empty(t), abs=1e-12), (rows, cols, w, t)
                assert float(m.expected_density_b(float(t))) == \
                    pytest.approx(m.expected_density(t),
                                  rel=1e-9), (rows, cols, w, t)
                assert float(m.max_nnz_b(float(t))) == m.max_nnz(t), \
                    (rows, cols, w, t)


def test_banded_batched_traceable_under_vmap():
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    m = BandedModel(rows=32, cols=32, half_band=2)
    assert m.batched
    with enable_x64():
        tiles = jnp.asarray([1.0, 4.0, 16.0, 64.0, 256.0])
        pe = jax.jit(jax.vmap(m.prob_empty_b))(tiles)
        ed = jax.jit(jax.vmap(m.expected_density_b))(tiles)
        for t, a, b in zip(tiles, pe, ed):
            assert float(a) == pytest.approx(m.prob_empty(int(t)),
                                             abs=1e-12)
            assert float(b) == pytest.approx(m.expected_density(int(t)),
                                             rel=1e-9)


def test_make_density_model_dispatch():
    assert isinstance(make_density_model(None, 10), DenseModel)
    assert isinstance(make_density_model(("uniform", 0.5), 10), UniformModel)
    assert isinstance(
        make_density_model(("structured", {"n": 2, "m": 4}), 16),
        StructuredModel)
    assert isinstance(
        make_density_model(("banded", {"rows": 8, "cols": 8,
                                       "half_band": 1}), 64), BandedModel)
    assert isinstance(
        make_density_model(("actual", np.ones((4, 4))), 16), ActualDataModel)
