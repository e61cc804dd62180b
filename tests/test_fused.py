"""Device-resident fused search (``repro.search.fused``).

The contracts under test: the traced decode matches the host
``decode_bucketed`` bit-for-bit; a fused run is bit-reproducible from
its key, runs with ZERO scalar evaluations and one scan compile per
(length, pop, genome) shape, and its winner is re-validated by the
scalar oracle; ineligible runs fall back to the host loop with a
warning; the end-to-end ``value_and_grad`` path through the bucketed
model matches central finite differences of the scalar oracle on every
ArchParams column (and of the traced surrogate loss itself); fused
generation records carry honest ``wall_time_s=None`` timing; and the
fused island mode routes chunk dispatches through the shared service.
"""
import dataclasses

import jax.numpy as jnp
import jax.random as jrandom
import numpy as np
import pytest
from jax import enable_x64

from repro.core import Sparseloop, compile_stats, matmul
from repro.core.arch import (ArchParams, COMPUTE_FIELDS, STORAGE_FIELDS,
                             pack_arch_params)
from repro.core.mapper import MapspaceConstraints
from repro.core.presets import (coordinate_list_design, scnn_like,
                                three_level_arch, two_level_arch)
from repro.search import (CoSearchEncoding, DesignSpace, GenerationRecord,
                          MapspaceEncoding, SearchLog, fused_supported,
                          get_fused_program, make_strategy, run_search)

WL = matmul(32, 32, 32, densities={"A": ("uniform", 0.3),
                                   "B": ("uniform", 0.3)})
DESIGN = coordinate_list_design(two_level_arch(buffer_kwords=8))
CONS = MapspaceConstraints(budget=96, seed=0, spatial={1: {"n": 4}})


def _space():
    return DesignSpace(
        capacity_steps={"Buffer": (2 * 1024, 8 * 1024, 64 * 1024)},
        extra_steps={("Buffer", "read_energy_pj"): (3.0, 6.0, 12.0)},
        compute_steps={"mac_energy_pj": (0.5, 1.0, 2.0)})


# ----------------------------------------------------------------------
# eligibility + traced decode parity
# ----------------------------------------------------------------------
def test_fused_supported():
    enc = MapspaceEncoding(WL, 2, CONS)
    assert fused_supported(enc)
    assert fused_supported(
        CoSearchEncoding(WL, 2, CONS, _space(), DESIGN))
    # a knob on a STATIC field (word_bits reshapes the trace) is not
    # traceable -> host loop only
    static = DesignSpace(extra_steps={("Buffer", "word_bits"):
                                      (8.0, 16.0)})
    assert not fused_supported(
        CoSearchEncoding(WL, 2, CONS, static, DESIGN))


@pytest.mark.parametrize("cons", [
    CONS,
    MapspaceConstraints(budget=96, seed=0),                 # no spatial
    MapspaceConstraints(budget=96, seed=0, spatial={1: {"n": 4}},
                        permutations={0: ("n", "k", "m"),
                                      1: ("m", "n")}),      # pinned order
])
def test_traced_decode_matches_host_decode(cons):
    enc = MapspaceEncoding(WL, 2, cons)
    pop = enc.random_population(jrandom.PRNGKey(0), 16)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = Sparseloop(DESIGN).bucketed_model(WL, bucket)
    fp = get_fused_program(bm, enc, make_strategy("es"))
    with enable_x64():
        for g, b_ref, i_ref in zip(pop, bounds, ids):
            b, i = fp._decode_map(jnp.asarray(g, jnp.int32))
            np.testing.assert_array_equal(np.asarray(b), b_ref)
            np.testing.assert_array_equal(np.asarray(i), i_ref)


# ----------------------------------------------------------------------
# fused runs: determinism, compile accounting, oracle-validated winner
# ----------------------------------------------------------------------
def test_fused_run_deterministic_and_validated():
    with compile_stats.track() as st:
        runs = [run_search(DESIGN, WL, CONS, strategy="es", key=5,
                           mesh=None, fused=True) for _ in range(2)]
    a, b = runs
    assert a.log.to_json(timing=False) == b.log.to_json(timing=False)
    # zero scalar evals, exactly one scan compile for both runs (the
    # FusedProgram is cached and both runs share one chunk shape)
    assert st.scalar_evals == 0
    assert st.compiles_by_kind.get("fused", 0) == 1
    # honest timing: generations inside the scan have no wall time,
    # chunk dispatches do
    assert all(r.wall_time_s is None for r in a.log.records)
    assert a.log.timing["fused"] is True
    assert sum(c["generations"] for c in a.log.timing["chunks"]) == \
        len(a.log.records)
    # the winner carries the host contract: scalar-oracle validated
    assert a.best is not None and a.best.result.valid
    oracle = Sparseloop(DESIGN).evaluate(WL, a.best_nest)
    assert a.best.edp == pytest.approx(oracle.edp, rel=1e-9)
    assert a.log.evaluations == len(a.log.records) * 32


@pytest.mark.parametrize("archive_k", [0, 4])
def test_fused_carry_survives_donation(archive_k):
    """Off the CPU each chunk donates its carry, and XLA refuses to
    donate one buffer twice: every leaf of the initial carry must own
    its buffer, and a chunk must run with the carry donated."""
    import jax
    enc = MapspaceEncoding(WL, 2, CONS)
    pop = enc.random_population(jrandom.PRNGKey(0), 32)
    bucket, _, _ = enc.decode_bucketed(pop)
    bm = Sparseloop(DESIGN).bucketed_model(WL, bucket)
    fp = get_fused_program(bm, enc, make_strategy("es"),
                           archive_k=archive_k)
    carry = fp.init_carry(0)
    leaves = jax.tree.leaves(carry)
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == len(leaves)
    with enable_x64():
        run = jax.jit(fp._chunk_fn(2).__wrapped__, donate_argnums=(0,))
        storage, comp = fp._base_params.leaves()
        out, _ = run(carry, fp.bm._bind_params(None),
                     jnp.asarray(storage, jnp.float64),
                     jnp.asarray(comp, jnp.float64))
    assert np.asarray(out[1]).shape == (32, enc.genome_size)


def test_fused_chunking_invariant():
    """Chunk boundaries are a dispatch artifact: the trajectory is
    identical whatever fused_chunk says."""
    from repro.search import SearchConfig
    logs = []
    for chunk in (2, 100):
        cfg = SearchConfig(fused_chunk=chunk)
        logs.append(run_search(DESIGN, WL, CONS, strategy="es", key=5,
                               mesh=None, fused=True, config=cfg).log)
    assert logs[0].to_json(timing=False) == logs[1].to_json(timing=False)


def test_fused_fallback_warns_and_matches_host():
    """A non-ES strategy is not fused-eligible: explicit fused=True
    warns and the run is byte-identical to the plain host run."""
    with pytest.warns(UserWarning, match="not fused-eligible"):
        fell_back = run_search(DESIGN, WL, CONS, strategy="hillclimb",
                               key=3, mesh=None, fused=True)
    host = run_search(DESIGN, WL, CONS, strategy="hillclimb", key=3,
                      mesh=None)
    assert fell_back.log.to_json(timing=False) == \
        host.log.to_json(timing=False)
    assert "fused" not in fell_back.log.timing


def test_fused_cosearch_with_hybrid_sgd():
    """Co-search (storage + compute knobs) through the fused path, with
    the Lamarckian SGD nudge on: deterministic, oracle-validated under
    the winner's own design, and no worse than the pure-ES fused run at
    equal budget."""
    space = _space()
    kw = dict(strategy="es", key=9, mesh=None, design_space=space,
              fused=True)
    runs = [run_search(DESIGN, WL, CONS, sgd_lr=0.5, **kw)
            for _ in range(2)]
    a, b = runs
    assert a.log.to_json(timing=False) == b.log.to_json(timing=False)
    assert a.best_design is not None
    oracle = Sparseloop(a.best_design).evaluate(WL, a.best_nest)
    assert a.best.result.valid
    assert a.best.edp == pytest.approx(oracle.edp, rel=1e-9)
    pure = run_search(DESIGN, WL, CONS, sgd_lr=0.0, **kw)
    assert a.best.edp <= pure.best.edp * (1 + 1e-9)


# ----------------------------------------------------------------------
# gradient parity: value_and_grad vs central finite differences
# ----------------------------------------------------------------------
def _oracle_edp(arch, nest):
    return Sparseloop(dataclasses.replace(DESIGN, arch=arch)).evaluate(
        WL, nest, check_capacity=False).edp


def _perturb_storage(arch, s, j, v):
    name = arch.level(s).name
    field = STORAGE_FIELDS[j]
    levels = tuple(dataclasses.replace(lv, **{field: v})
                   if lv.name == name else lv for lv in arch.levels)
    return dataclasses.replace(arch, levels=levels)


def _perturb_compute(arch, j, v):
    field = COMPUTE_FIELDS[j]
    v = int(round(v)) if field == "instances" else v
    return dataclasses.replace(
        arch, compute=dataclasses.replace(arch.compute, **{field: v}))


def test_arch_grad_matches_scalar_oracle_fd():
    """d(EDP)/d(column) from one value_and_grad pass matches a central
    finite difference of the SCALAR oracle <= 1e-3 relative, for every
    finite ArchParams storage and compute column (plateaued columns —
    capacity, bandwidth — agree on zero)."""
    enc = MapspaceEncoding(WL, 2, CONS)
    pop = enc.random_population(jrandom.PRNGKey(0), 8)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = Sparseloop(DESIGN).bucketed_model(WL, bucket,
                                           check_capacity=True)
    out = bm.evaluate_with_arch_grad(bounds, ids, metric="edp")
    assert out["grad_storage"].shape == (8, 2, len(STORAGE_FIELDS))
    assert out["grad_compute"].shape == (8, len(COMPUTE_FIELDS))
    c = int(np.flatnonzero(out["valid"])[0])
    nest = enc.nest_of(pop[c])
    arch = DESIGN.arch
    ap = pack_arch_params(arch)
    scale = abs(float(out["edp"][c]))

    def check(g, fd):
        if abs(fd) < 1e-12 * scale:
            assert abs(g) < 1e-9 * scale
        else:
            assert g == pytest.approx(fd, rel=1e-3)

    for s in range(2):
        for j in range(len(STORAGE_FIELDS)):
            x = float(ap.storage[s, j])
            if not np.isfinite(x):
                continue
            h = 1e-4 * max(abs(x), 1.0)
            fd = (_oracle_edp(_perturb_storage(arch, s, j, x + h), nest)
                  - _oracle_edp(_perturb_storage(arch, s, j, x - h),
                                nest)) / (2 * h)
            check(float(out["grad_storage"][c, s, j]), fd)
    for j, field in enumerate(COMPUTE_FIELDS):
        x = float(ap.compute[j])
        h = 1.0 if field == "instances" else 1e-4 * max(abs(x), 1.0)
        fd = (_oracle_edp(_perturb_compute(arch, j, x + h), nest)
              - _oracle_edp(_perturb_compute(arch, j, x - h),
                            nest)) / (2 * h)
        check(float(out["grad_compute"][c, j]), fd)


def test_surrogate_grad_matches_traced_fd():
    """The smooth capacity-surrogate loss is consistent with its own
    gradients: FD of the traced loss w.r.t. perturbed ArchParams rows
    matches grad_storage <= 1e-3 relative — including the capacity
    column, which the surrogate (unlike the hard mask) makes
    differentiable."""
    enc = MapspaceEncoding(WL, 2, CONS)
    pop = enc.random_population(jrandom.PRNGKey(1), 4)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = Sparseloop(DESIGN).bucketed_model(WL, bucket,
                                           check_capacity=True)
    ap = pack_arch_params(DESIGN.arch)
    out = bm.evaluate_with_arch_grad(bounds, ids, metric="edp",
                                     surrogate=True, tau=0.05)
    assert np.isfinite(out["loss"]).all()
    c = int(np.flatnonzero(out["valid"])[0])

    def loss_at(storage):
        pert = ArchParams(storage=storage, compute=ap.compute,
                          structure=ap.structure)
        return float(bm.evaluate_with_arch_grad(
            bounds, ids, arch_params=pert, metric="edp",
            surrogate=True, tau=0.05)["loss"][c])

    for (s, j) in [(0, STORAGE_FIELDS.index("capacity_words")),
                   (0, STORAGE_FIELDS.index("read_energy_pj")),
                   (1, STORAGE_FIELDS.index("metadata_read_energy_pj"))]:
        x = float(ap.storage[s, j])
        h = 1e-5 * max(abs(x), 1.0)
        up = np.array(ap.storage)
        up[s, j] = x + h
        dn = np.array(ap.storage)
        dn[s, j] = x - h
        fd = (loss_at(up) - loss_at(dn)) / (2 * h)
        g = float(out["grad_storage"][c, s, j])
        if abs(fd) < 1e-12:
            assert abs(g) < 1e-9
        else:
            assert g == pytest.approx(fd, rel=1e-3)


# ----------------------------------------------------------------------
# SearchLog timing honesty for fused records
# ----------------------------------------------------------------------
def test_log_none_wall_time_roundtrip():
    log = SearchLog(strategy="es", metric="edp")
    log.append(GenerationRecord(0, 32, 30, 1.0, 1.0, 1.0, 1.0,
                                wall_time_s=None))
    log.append(GenerationRecord(1, 64, 60, 0.5, 1.0, 1.0, 0.5,
                                wall_time_s=0.25))
    # the measurable sum skips fused (None) generations
    assert log.wall_time_s == 0.25
    back = SearchLog.from_json(log.to_json())
    assert back.records[0].wall_time_s is None
    assert back.records[1].wall_time_s == 0.25
    # pre-flight-recorder logs without the field still load (default 0.0)
    old = {"generation": 0, "evaluations": 8, "valid": 8,
           "best_fitness": 1.0, "best_cycles": 1.0,
           "best_energy_pj": 1.0, "best_edp": 1.0}
    assert GenerationRecord.from_dict(old).wall_time_s == 0.0
    # timing=False strips wall_time_s entirely (the reproducibility form)
    assert "wall_time_s" not in log.to_dict(timing=False)["records"][0]


# ----------------------------------------------------------------------
# service + islands integration
# ----------------------------------------------------------------------
def test_service_fused_requests():
    from repro.dse import EvaluationService
    with EvaluationService(autostart=False) as svc:
        client = svc.client("t0")
        carry, ys = client.run_fused(lambda: ("carry", {"n": 1}))
        assert carry == "carry" and ys == {"n": 1}
        assert svc.stats()["fused_chunks"] == 1
        assert svc.stats()["batches"] == 0


def test_islands_fused_mode():
    from repro.dse import run_islands
    design = scnn_like(three_level_arch())
    wl = matmul(64, 48, 32, densities={"A": ("uniform", 0.4),
                                       "B": ("uniform", 0.6)})
    cons = MapspaceConstraints(budget=256, seed=0, spatial={1: {"n": 8}})
    r = run_islands(design, wl, cons, n_islands=2, generations=4,
                    migrate_every=2, key=0, fused=True)
    # 2 islands x 2 chunks, all through the service's fused path
    assert r.service_stats["fused_chunks"] == 4
    assert r.service_stats["batches"] == 0
    assert r.best.best is not None and r.best.best.result.valid
    assert r.evaluations == 2 * 4 * 32
    assert all(rec.wall_time_s is None
               for lg in r.logs for rec in lg.records)
