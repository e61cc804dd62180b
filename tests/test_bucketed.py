"""Bucketed padded-template lowering (core.batched.TemplateBucket /
BucketedModel) parity and compile accounting.

The contract under test: a mixed-permutation population lowered onto ONE
padded bucket program reproduces both the per-exact-template batched
path and the scalar reference oracle to <= 1e-6 relative — across design
families, banded (coordinate-dependent) densities, and 1-level /
unit-bound edge cases — while compiling no more programs than the bucket
bound (``repro.core.compile_stats`` counts them, and the search runner's
``SearchConfig`` dispatch is env-forcible both ways)."""
import numpy as np
import pytest
import jax.random as jrandom

from repro.core import Sparseloop, compile_stats, matmul
from repro.core.arch import Architecture, ComputeLevel, StorageLevel
from repro.core.batched import (TemplateBucket, bucket_for,
                                get_bucketed_model, group_by_bucket,
                                template_of)
from repro.core.mapper import MapspaceConstraints, search
from repro.core.mapping import nest
from repro.core.presets import (bitmask_design, coordinate_list_design,
                                dense_design, two_level_arch)
from repro.search import MapspaceEncoding, SearchConfig, run_search
from repro.search.runner import PopulationEvaluator

M = N = K = 16
ARCH = two_level_arch(buffer_kwords=64)
WL = matmul(M, K, N, densities={"A": ("uniform", 0.25),
                                "B": ("uniform", 0.5)})
#: free permutations at every level -> genomes span many loop orders
CONS = MapspaceConstraints(budget=96, seed=0, spatial={1: {"n": 4}})


def _population(wl, num_levels, cons, n, key=1, n_perms=None):
    """Random population; ``n_perms`` caps the number of distinct loop
    orders (bounds the per-exact-template comparison's compile bill
    without reducing factor diversity)."""
    enc = MapspaceEncoding(wl, num_levels, cons)
    pop = enc.random_population(jrandom.PRNGKey(key), n)
    if n_perms is not None and enc.perm_levels:
        pool = pop[:n_perms, enc.num_factor_genes:]
        pop[:, enc.num_factor_genes:] = pool[np.arange(n) % len(pool)]
    return enc, pop


# ----------------------------------------------------------------------
# bucket structure
# ----------------------------------------------------------------------
def test_bucket_fits_lower_roundtrip():
    enc, pop = _population(WL, 2, CONS, 8)
    bucket = enc.bucket
    assert bucket.temporal_slots == (3, 3)      # all ranks, each level
    assert bucket.spatial_slots == (0, 1)       # the forced n-spatial
    for g in pop:
        nest = enc.nest_of(g)
        template = template_of(nest)
        assert bucket.fits(template)
        assert bucket_for(template, bucket.ranks) == bucket
        slot_map = bucket.lower(template)
        layout = bucket.slot_layout()
        # levels and spatial flags preserved, order within level kept
        for i, (r, lvl, sp) in enumerate(template.slots):
            assert layout[slot_map[i]] == (lvl, sp)
        pb, ids = bucket.lower_population(
            template, template.bounds_of(nest)[None, :])
        live = [(bucket.ranks[ids[0, j]], lvl, sp)
                for j, (lvl, sp) in enumerate(layout) if pb[0, j] > 1]
        assert tuple(live) == nest.structure()


def test_bucket_rejects_misfit_templates():
    bucket = TemplateBucket(ranks=("m", "k", "n"),
                            temporal_slots=(1, 1), spatial_slots=(0, 0))
    big = template_of(nest(2, ("m", 2, 1), ("n", 2, 1), ("k", 4, 0)))
    assert not bucket.fits(big)          # level 1 needs 2 temporal slots
    with pytest.raises(ValueError):
        bucket.lower(big)
    ok = template_of(nest(2, ("m", 4, 1), ("k", 4, 0)))
    assert bucket.fits(ok)


# ----------------------------------------------------------------------
# parity: padded bucket vs exact template vs scalar oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("maker", [dense_design, bitmask_design,
                                   coordinate_list_design])
def test_bucketed_parity_mixed_permutations(maker):
    """One bucket program evaluates a mixed-permutation population;
    cycles AND energy AND edp <= 1e-6 rel vs the scalar oracle, and the
    per-exact-template batched path agrees too."""
    design = maker(ARCH)
    model = Sparseloop(design)
    # cap at 6 distinct loop orders: the exact-template comparison below
    # compiles one program per order, and compile time is what it costs
    enc, pop = _population(WL, 2, CONS, 48, n_perms=6)
    n_templates = len(enc.decode_population(pop))
    assert n_templates >= 4          # genuinely mixed loop orders

    bucket, bounds, ids = enc.decode_bucketed(pop)
    out = get_bucketed_model(design, WL, bucket,
                             check_capacity=False).evaluate(bounds, ids)
    # exact-template reference: one compiled program per loop order
    # (dense only — compile time is what it costs; the scalar oracle
    # below is the authoritative reference for every design)
    exact = np.full(len(pop), np.nan)
    if maker is dense_design:
        for template, idx, tb in enc.decode_population(pop):
            res = model.batched_model(
                WL, template, check_capacity=False).evaluate(tb)
            exact[idx] = res["edp"]
    for i, g in enumerate(pop):
        ev = model.evaluate(WL, enc.nest_of(g), check_capacity=False)
        assert out["cycles"][i] == pytest.approx(ev.cycles, rel=1e-6)
        assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                    rel=1e-6)
        assert out["edp"][i] == pytest.approx(ev.edp, rel=1e-6)
        if not np.isnan(exact[i]):
            assert exact[i] == pytest.approx(ev.edp, rel=1e-6)


def test_bucketed_parity_banded_density():
    """Coordinate-dependent banded statistics survive the padded
    lowering (rank-id gathers feed the same closed forms)."""
    wl = matmul(M, K, N, densities={
        "A": ("banded", {"rows": M, "cols": K, "half_band": 2}),
        "B": ("uniform", 0.5)})
    design = coordinate_list_design(ARCH)
    model = Sparseloop(design)
    enc, pop = _population(wl, 2, CONS, 24, key=3)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    out = get_bucketed_model(design, wl, bucket,
                             check_capacity=False).evaluate(bounds, ids)
    for i, g in enumerate(pop):
        ev = model.evaluate(wl, enc.nest_of(g), check_capacity=False)
        assert out["cycles"][i] == pytest.approx(ev.cycles, rel=1e-6)
        assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                    rel=1e-6)


def test_bucketed_parity_one_level_arch_and_unit_bounds():
    """Edge cases: a single storage level, plus a unit-bound rank (k=1
    has no factor genes — its slots ride as permanent unit padding)."""
    arch1 = Architecture(
        name="one-level",
        levels=(StorageLevel("Buffer", float("inf"), 64, 6.0),),
        compute=ComputeLevel("MAC", instances=4))
    wl = matmul(8, 1, 4, densities={"A": ("uniform", 0.5)})
    design = dense_design(arch1)
    model = Sparseloop(design)
    cons = MapspaceConstraints(budget=32, seed=0)
    enc, pop = _population(wl, 1, cons, 16, key=5)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    assert bucket.temporal_slots == (3,) and bucket.spatial_slots == (0,)
    out = get_bucketed_model(design, wl, bucket,
                             check_capacity=False).evaluate(bounds, ids)
    for i, g in enumerate(pop):
        ev = model.evaluate(wl, enc.nest_of(g), check_capacity=False)
        assert out["cycles"][i] == pytest.approx(ev.cycles, rel=1e-6)
        assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                    rel=1e-6)


def test_bucketed_capacity_validity_matches_scalar():
    design = coordinate_list_design(two_level_arch(buffer_kwords=0.06))
    model = Sparseloop(design)
    enc, pop = _population(WL, 2, CONS, 32, key=7)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    out = get_bucketed_model(design, WL, bucket,
                             check_capacity=True).evaluate(bounds, ids)
    ref = [model.evaluate(WL, enc.nest_of(g)).result.valid for g in pop]
    assert out["valid"].tolist() == ref
    assert 0 < sum(ref) < len(ref)   # the check actually separates


# ----------------------------------------------------------------------
# dispatch + compile accounting
# ----------------------------------------------------------------------
def test_evaluate_batch_buckets_mixed_population():
    """The public evaluate_batch lowers a mixed-permutation population
    onto bucket-bound many programs (here: one)."""
    design = dense_design(ARCH)
    model = Sparseloop(design)
    enc, pop = _population(WL, 2, CONS, 32, key=9)
    nests = [enc.nest_of(g) for g in pop]
    assert len(group_by_bucket(nests, tuple(WL.rank_bounds))) == 1
    with compile_stats.track() as st:
        out = model.evaluate_batch(WL, nests, check_capacity=False)
    assert out["cycles"].shape == (len(nests),)
    assert st.compiles_by_kind.get("bucket", 0) <= 1
    assert st.compiles_by_kind.get("template", 0) == 0


def test_compile_stats_counts_programs_and_shapes():
    from repro.core.batched import clear_caches
    clear_caches()        # exact compile counts need a cold cache
    wl = matmul(8, 8, 8, densities={"A": ("uniform", 0.5)})
    design = dense_design(two_level_arch())
    enc = MapspaceEncoding(wl, 2, MapspaceConstraints(seed=0))
    pop = enc.random_population(jrandom.PRNGKey(0), 8)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    with compile_stats.track() as st:
        bm = get_bucketed_model(design, wl, bucket, check_capacity=False)
        bm.evaluate(bounds, ids)           # compile (new shape)
        bm.evaluate(bounds, ids)           # cached: same shape
        bm.evaluate(bounds[:4], ids[:4])   # compile (new shape)
        get_bucketed_model(design, wl, bucket, check_capacity=False)
    assert st.compiles == 2
    assert st.cache_hits >= 1
    assert st.batched_evals == 8 + 8 + 4
    assert st.scalar_evals == 0


def test_free_permutation_es_fully_batched():
    """Acceptance pin: free-permutation ES rides the bucketed engine end
    to end — zero scalar-path evaluations, compile count <= the bucket
    bound (one bucket for one (workload, spatial-shape) slice)."""
    design = coordinate_list_design(two_level_arch(buffer_kwords=8))
    wl = matmul(32, 32, 32, densities={"A": ("uniform", 0.3),
                                       "B": ("uniform", 0.3)})
    with compile_stats.track() as st:
        res = run_search(design, wl, CONS, strategy="es", key=11,
                         mesh=None)
    assert res.best is not None and res.best.result.valid
    assert st.scalar_evals == 0
    assert st.compiles <= 1, st.as_dict()
    assert st.compiles_by_kind.get("template", 0) == 0


def test_search_config_env_override(monkeypatch):
    """The scalar-fallback threshold is an explicit SearchConfig field
    read from the environment, so CI can force either path."""
    monkeypatch.setenv("REPRO_SEARCH_BATCH_THRESHOLD", "1000000")
    assert SearchConfig().batch_threshold == 1000000
    monkeypatch.setenv("REPRO_SEARCH_BATCH_THRESHOLD", "7")
    assert SearchConfig().batch_threshold == 7
    monkeypatch.setenv("REPRO_SEARCH_BATCH_THRESHOLD", "zap")
    with pytest.raises(ValueError, match="REPRO_SEARCH_BATCH_THRESHOLD"):
        SearchConfig()
    monkeypatch.delenv("REPRO_SEARCH_BATCH_THRESHOLD")
    monkeypatch.setenv("REPRO_SEARCH_BUCKETED", "0")
    assert SearchConfig().bucketed is False
    monkeypatch.delenv("REPRO_SEARCH_BUCKETED")
    assert SearchConfig().bucketed is True


def test_search_config_env_validation_warns(monkeypatch):
    """Unknown REPRO_SEARCH_* names and non-canonical boolean values
    warn instead of silently no-op'ing / silently coercing."""
    import warnings as _warnings
    monkeypatch.setenv("REPRO_SEARCH_BUKETED", "0")       # typo'd name
    with pytest.warns(UserWarning, match="REPRO_SEARCH_BUKETED"):
        SearchConfig()
    monkeypatch.delenv("REPRO_SEARCH_BUKETED")
    monkeypatch.setenv("REPRO_SEARCH_BUCKETED", "maybe")
    with pytest.warns(UserWarning, match="not a recognized boolean"):
        cfg = SearchConfig()
    assert cfg.bucketed is True      # legacy coercion, now loud
    monkeypatch.delenv("REPRO_SEARCH_BUCKETED")
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")      # clean env: no warnings
        SearchConfig()


def test_search_config_forces_both_paths_deterministically():
    """Same key, scalar-forced vs bucket-forced dispatch: identical
    winner (to round-off), and the compile counters prove which path
    actually ran."""
    design = coordinate_list_design(two_level_arch(buffer_kwords=8))
    wl = matmul(32, 32, 32, densities={"A": ("uniform", 0.3),
                                       "B": ("uniform", 0.3)})
    cons = MapspaceConstraints(budget=48, seed=0, spatial={1: {"n": 4}})

    with compile_stats.track() as st_scalar:
        r_scalar = run_search(
            design, wl, cons, strategy="es", key=4, pop_size=16,
            mesh=None, config=SearchConfig(batch_threshold=10 ** 18))
    assert st_scalar.scalar_evals == r_scalar.evaluated > 0

    with compile_stats.track() as st_bucket:
        r_bucket = run_search(
            design, wl, cons, strategy="es", key=4, pop_size=16,
            mesh=None, config=SearchConfig(batch_threshold=1))
    assert st_bucket.scalar_evals == 0

    assert r_scalar.best_nest == r_bucket.best_nest
    assert r_scalar.best.edp == pytest.approx(r_bucket.best.edp,
                                              rel=1e-6)


def test_population_evaluator_bucketed_off_uses_templates():
    design = dense_design(ARCH)
    enc, pop = _population(WL, 2, CONS, 48, key=13, n_perms=3)
    ev_bucket = PopulationEvaluator(
        design, WL, enc, config=SearchConfig(batch_threshold=1,
                                             bucketed=True))
    ev_templ = PopulationEvaluator(
        design, WL, enc, config=SearchConfig(batch_threshold=1,
                                             bucketed=False))
    with compile_stats.track() as st:
        a = ev_bucket(pop)
        b = ev_templ(pop)
    assert st.compiles_by_kind.get("bucket", 0) <= 1
    assert st.compiles_by_kind.get("template", 0) >= 2
    finite = np.isfinite(a["edp"])
    assert (finite == np.isfinite(b["edp"])).all()
    np.testing.assert_allclose(a["edp"][finite], b["edp"][finite],
                               rtol=1e-6)


# ----------------------------------------------------------------------
# workload-as-data: one compiled program across layers / density kinds
# ----------------------------------------------------------------------
def test_shared_program_across_layers_uniform():
    """Layers with different rank bounds and densities but equal
    *structure* evaluate through ONE compiled bucket program: the rank
    bounds and density parameters are traced WorkloadParams, not trace
    constants.  Parity vs the per-layer scalar oracle."""
    from repro.core.batched import clear_caches
    clear_caches()        # exact program/compile counts: cold cache
    design = dense_design(two_level_arch(buffer_kwords=62))
    model = Sparseloop(design)
    layers = [matmul(16, 16, 16, densities={"A": ("uniform", 0.25)}),
              matmul(32, 8, 16, densities={"A": ("uniform", 0.5),
                                           "B": ("uniform", 0.7)}),
              matmul(8, 32, 16)]
    pops, nests = [], []
    for i, wl in enumerate(layers):
        enc, pop = _population(wl, 2, CONS, 12, key=20 + i)
        pops.append((enc, pop))
        nests.append([enc.nest_of(g) for g in pop])
    with compile_stats.track() as st:
        outs = model.evaluate_network(layers, nests,
                                      check_capacity=False)
    assert st.programs == 1, st.as_dict()
    assert st.compiles == 1, st.as_dict()
    assert st.program_shares >= len(layers) - 1
    # layers after the first ran program-shared, the first specialized
    assert st.shared_evals == 2 * 12 and st.batched_evals == 3 * 12
    for wl, (enc, pop), out in zip(layers, pops, outs):
        for i, g in enumerate(pop):
            ev = model.evaluate(wl, enc.nest_of(g), check_capacity=False)
            assert out["cycles"][i] == pytest.approx(ev.cycles, rel=1e-6)
            assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                        rel=1e-6)


def test_shared_program_mixed_density_kinds():
    """A uniform layer, a banded layer and an actual-data layer — the
    density *kind* is traced data too (model-id switch + tile-occupancy
    histogram), so all three share one compiled program under common
    caps.  Parity <= 1e-6 vs the scalar oracle for every layer."""
    from repro.core.batched import clear_caches
    clear_caches()        # exact program/compile counts: cold cache
    rng = np.random.default_rng(11)
    design = coordinate_list_design(two_level_arch(buffer_kwords=59))
    model = Sparseloop(design)
    layers = [
        matmul(M, K, N, densities={"A": ("uniform", 0.3),
                                   "B": ("uniform", 0.6)}),
        matmul(M, K, N, densities={
            "A": ("banded", {"rows": M, "cols": K, "half_band": 2})}),
        matmul(M, K, N, densities={
            "A": ("actual", (rng.random((M, K)) < 0.35).astype(float)),
            "B": ("uniform", 0.5)}),
    ]
    pops, nests = [], []
    for i, wl in enumerate(layers):
        enc, pop = _population(wl, 2, CONS, 10, key=30 + i)
        pops.append((enc, pop))
        nests.append([enc.nest_of(g) for g in pop])
    with compile_stats.track() as st:
        outs = model.evaluate_network(layers, nests,
                                      check_capacity=False)
    assert st.programs == 1 and st.compiles == 1, st.as_dict()
    assert st.scalar_evals == 0
    for wl, (enc, pop), out in zip(layers, pops, outs):
        for i, g in enumerate(pop):
            ev = model.evaluate(wl, enc.nest_of(g), check_capacity=False)
            assert out["cycles"][i] == pytest.approx(ev.cycles, rel=1e-6)
            assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                        rel=1e-6)
            assert out["edp"][i] == pytest.approx(ev.edp, rel=1e-6)


def test_workload_params_caps_mismatch_raises():
    """Binding params packed under different caps to a program is a
    loud error, not a silent shape-triggered recompile."""
    from repro.core.batched import (DensityCaps, get_bucketed_model,
                                    pack_workload_params)
    design = dense_design(two_level_arch(buffer_kwords=58))
    enc, pop = _population(WL, 2, CONS, 4, key=41)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = get_bucketed_model(design, WL, bucket, check_capacity=False)
    wrong = pack_workload_params(WL, caps=DensityCaps(hist=64))
    with pytest.raises(ValueError, match="caps"):
        bm.evaluate(bounds, ids, workload_params=wrong)
    # params packed for a structurally different workload are rejected
    from repro.core.workload import conv2d
    other = pack_workload_params(conv2d(1, 4, 4, 4, 4, 3, 3))
    with pytest.raises(ValueError, match="structure"):
        bm.evaluate(bounds, ids, workload_params=other)


def test_stack_workload_params_rejects_mixed_rows():
    """Rows of different workloads share one candidate axis only when
    they share the program's caps and structure; the row path refuses
    a stack whose row count is not the population's."""
    import dataclasses
    from repro.core.batched import (DensityCaps, pack_workload_params,
                                    stack_workload_params)
    from repro.core.workload import TensorSpec
    a = pack_workload_params(matmul(8, 16, 32))
    b = pack_workload_params(matmul(64, 16, 8,
                                    densities={"B": ("uniform", 0.5)}))
    stacked = stack_workload_params([a, b])
    assert stacked.rank_bounds.shape == (2, 3)
    assert stacked.caps == a.caps and stacked.structure == a.structure
    np.testing.assert_array_equal(stacked.rank_bounds[1], b.rank_bounds)
    np.testing.assert_array_equal(stacked.density_params[1],
                                  b.density_params)
    wide = pack_workload_params(matmul(8, 16, 32),
                                caps=DensityCaps(hist=64))
    with pytest.raises(ValueError, match="caps"):
        stack_workload_params([a, wide])
    # same rank names and tensor count, B stored (n, k): the arrays
    # would stack, the structure check refuses
    wl = matmul(8, 16, 32)
    transposed = dataclasses.replace(wl, tensors=(
        wl.tensors[0], TensorSpec("B", (("n",), ("k",))), wl.tensors[2]))
    with pytest.raises(ValueError, match="structure"):
        stack_workload_params([a, pack_workload_params(transposed)])
    with pytest.raises(ValueError):
        stack_workload_params([])
    design = dense_design(two_level_arch(buffer_kwords=58))
    enc, pop = _population(WL, 2, CONS, 4, key=41)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = get_bucketed_model(design, WL, bucket, check_capacity=False)
    one = stack_workload_params([pack_workload_params(WL)])
    with pytest.raises(ValueError, match="shape"):
        bm.evaluate_rows(bounds, ids, one)


def test_program_cache_never_serves_stale_energies():
    """Regression (cache-key audit): two designs differing ONLY in a
    derived-default-adjacent scalar (gated_energy_pj) share one traced
    program — arch scalars are traced ArchParams now — but each facade
    binds its own params, so neither ever sees the other's energies."""
    import dataclasses
    from repro.core.batched import clear_caches
    clear_caches()
    lo = two_level_arch(buffer_kwords=64)
    hi = dataclasses.replace(
        lo, levels=(lo.levels[0],
                    dataclasses.replace(lo.levels[1],
                                        gated_energy_pj=50.0)))
    assert lo.canonical() != hi.canonical()
    d_lo, d_hi = bitmask_design(lo), bitmask_design(hi)
    enc, pop = _population(WL, 2, CONS, 12, key=17)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    with compile_stats.track() as st:
        m_lo = get_bucketed_model(d_lo, WL, bucket, check_capacity=False)
        m_hi = get_bucketed_model(d_hi, WL, bucket, check_capacity=False)
        out_lo = m_lo.evaluate(bounds, ids)
        out_hi = m_hi.evaluate(bounds, ids)
    assert m_lo is not m_hi               # facades never alias
    assert st.programs == 1               # ... but the program is shared
    # gating in the bitmask design makes the energies genuinely differ
    assert (out_hi["energy_pj"] > out_lo["energy_pj"]).all()
    for out, d in ((out_lo, d_lo), (out_hi, d_hi)):
        model = Sparseloop(d)
        for i in (0, 5, 11):
            ev = model.evaluate(WL, enc.nest_of(pop[i]),
                                check_capacity=False)
            assert out["energy_pj"][i] == pytest.approx(ev.energy_pj,
                                                        rel=1e-6)


def test_storage_level_canonical_resolves_sentinels():
    """The -1.0 construction sentinels (write/metadata energy derived
    from read energy) resolve before cache keying: a level built with
    defaults and one built with the explicit derived values alias; any
    real scalar difference never does."""
    from repro.core.arch import StorageLevel
    a = StorageLevel("Buf", 1024, 64, 6.0)
    b = StorageLevel("Buf", 1024, 64, 6.0, write_energy_pj=6.0,
                     metadata_read_energy_pj=1.5)
    assert a.canonical() == b.canonical()
    c = StorageLevel("Buf", 1024, 64, 6.0, gated_energy_pj=0.5)
    assert a.canonical() != c.canonical()
    arch_a = two_level_arch()
    arch_b = two_level_arch()
    assert arch_a.canonical() == arch_b.canonical()
    # canonical-keyed facade cache: equal-after-derivation archs hit
    enc, pop = _population(WL, 2, CONS, 4, key=19)
    bucket, _, _ = enc.decode_bucketed(pop)
    with compile_stats.track() as st:
        m1 = get_bucketed_model(dense_design(arch_a), WL, bucket)
        m2 = get_bucketed_model(dense_design(arch_b), WL, bucket)
    assert m1 is m2 and st.cache_hits >= 1


def test_track_robust_to_midblock_reset_and_clear():
    """Satellite pin: compile_stats.track() snapshot-subtract survives a
    mid-block reset() + clear_caches() in either order — the delta is
    the post-reset activity, never negative, never double-counted."""
    from repro.core.batched import clear_caches
    wl = matmul(8, 8, 8, densities={"A": ("uniform", 0.5)})
    design = dense_design(two_level_arch())
    enc = MapspaceEncoding(wl, 2, MapspaceConstraints(seed=0))
    pop = enc.random_population(jrandom.PRNGKey(2), 4)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    clear_caches()
    with compile_stats.track() as st:
        get_bucketed_model(design, wl, bucket,
                           check_capacity=False).evaluate(bounds, ids)
        # discard history mid-block, in both orderings
        compile_stats.reset()
        clear_caches()
        get_bucketed_model(design, wl, bucket,
                           check_capacity=False).evaluate(bounds, ids)
        clear_caches()
        compile_stats.reset()
        get_bucketed_model(design, wl, bucket,
                           check_capacity=False).evaluate(bounds, ids)
    # exactly the post-LAST-reset activity: one program, one compile,
    # one population — no negative counters, no double-counting
    assert st.programs == 1 and st.compiles == 1
    assert st.batched_evals == len(pop)
    assert all(v >= 0 for v in (st.programs, st.compiles, st.cache_hits,
                                st.batched_evals, st.scalar_evals))


def test_mapper_free_permutation_search_batched_vs_scalar():
    """Pin: the bucket-grouped enumeration dispatch finds the identical
    best-EDP mapping as the scalar loop on a FREE-permutation mapspace
    slice (the constrained-slice regression lives in test_batched)."""
    wl = matmul(32, 32, 32, densities={"A": ("uniform", 0.3),
                                       "B": ("uniform", 0.3)})
    design = coordinate_list_design(two_level_arch(buffer_kwords=8))
    cons = MapspaceConstraints(budget=80, seed=3, spatial={1: {"n": 4}})
    scalar = search(design, wl, cons, use_batched=False)
    with compile_stats.track() as st:
        batched = search(design, wl, cons, use_batched=True)
    assert st.compiles_by_kind.get("template", 0) == 0
    assert scalar.best_nest == batched.best_nest
    assert batched.best.edp == pytest.approx(scalar.best.edp, rel=1e-9)
    assert (scalar.evaluated, scalar.valid) == (batched.evaluated,
                                                batched.valid)
