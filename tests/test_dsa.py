"""DeepSeek-V3.2-Exp's sparse attention (DSA) through the modeler.

* the configuration's parameters, and the decode extraction's
  parameter and FLOP exactness, W_UK + W_UV = kv_b included;
* the selection density, scalar against traced and against a seeded
  Monte-Carlo draw of k of L fibres, and its branch pruned from programs
  that have no selected tensor;
* the row path against the scalar oracle on selected rows, the sweep's
  acceptance (selected rows, the ``dsa-skip`` option, compiles, the
  context crossover) and a fleet6 sweep unchanged row for row;
* the absorbed einsum chain the extraction emits, executed, against the
  substrate's MLA forward with the indexer's top-k mask.
"""
import dataclasses
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.configs import get_config
from repro.core import compile_stats
from repro.core.advisor import tpu_mapping
from repro.core.density import (MODEL_KINDS, SELECT_PRODUCT, DensityCaps,
                                SelectedModel, TracedDensityStats,
                                caps_for_models, make_density_model)
from repro.core.engine import Sparseloop
from repro.core.presets import dense_design, tpu_dsa_design, tpu_v5e_arch
from repro.core.workload import matmul
from repro.fleet.extract import LayerMatmul, extract_network
from repro.fleet.sweep import dedupe_shapes, dsa_option, fleet_sweep
from repro.models import layers as L

DATA = pathlib.Path(__file__).parent / "data"
CTX_GRID = (4096, 8192, 16384, 32768, 65536, 131072, 163840)


# ----------------------------------------------------------------------
# configuration and extraction
# ----------------------------------------------------------------------

def test_param_count_matches_published():
    cfg = get_config("deepseek-v3.2-exp")
    d, h, v = 7168, 128, 129280
    attn = (d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256
            + h * 128 * d)
    indexer = 1536 * 64 * 128 + d * 128 + d * 64
    moe = 256 * 3 * d * 2048 + 3 * d * 2048 + d * 256
    want = 2 * v * d + 61 * (attn + indexer) + 3 * 3 * d * 18432 + 58 * moe
    assert cfg.param_count() == want
    # 671 B published, the indexer adds 0.85 B
    assert abs(cfg.param_count() - 671.9e9) / 671.9e9 < 2e-3


@pytest.mark.parametrize("reduced", [False, True])
def test_decode_parameter_exactness(reduced):
    cfg = get_config("deepseek-v3.2-exp", reduced=reduced)
    m, h = cfg.mla, cfg.num_heads
    dec = extract_network(cfg, "decode", batch=4, ctx_len=64)
    assert dec.total_params == cfg.param_count()
    by = {e.name: e for e in dec.matmuls}
    # the absorbed W_UK and W_UV are kv_b's parameters, split per head
    kv_b = m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
    assert (by["mla_uk"].weight_params + by["mla_uv"].weight_params
            == cfg.num_layers * kv_b)
    pre = {e.name: e for e in extract_network(
        cfg, "prefill", seq_len=16, batch=2).matmuls}
    assert pre["mla_kv_b_proj"].weight_params == cfg.num_layers * kv_b
    assert all(e.select is None for e in pre.values())


def test_decode_flops_closed_form():
    cfg = get_config("deepseek-v3.2-exp")
    d, h, V, dff = 7168, 128, 129280, 18432
    ql, kvl, nope, rope, vd = 1536, 512, 128, 64, 128
    ih, idim = 64, 128
    E, topk, ef = 256, 8, 2048
    B, C = 256, 131072
    T = B
    tok = T * topk // E
    per_layer = 2 * T * (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
                         + h * nope * kvl + h * kvl * vd + h * vd * d
                         + ql * ih * idim + d * idim + d * ih)
    attn = B * (2 * ih * idim * C + 2 * h * (kvl + rope) * C
                + 2 * h * C * kvl)
    dense_ffn = 2 * T * (d * 2 * dff + dff * d)
    moe = (2 * T * d * E + E * 2 * tok * (d * 2 * ef + ef * d)
           + 2 * T * (d * 2 * ef + ef * d))
    want = 61 * (per_layer + attn) + 3 * dense_ffn + 58 * moe \
        + 2 * T * d * V
    dec = extract_network(cfg, "decode", batch=B, ctx_len=C)
    assert dec.total_flops == want
    by = {e.name: e for e in dec.matmuls}
    # the latent cache is the selection in both products: its columns
    # in the score product, its rows in the value product
    assert by["dsa_qk"].densities == {
        "B": ("selected", {"rank": "n", "L": C, "k": 2048, "fibre": 576})}
    assert by["dsa_av"].densities == {
        "B": ("selected", {"rank": "k", "L": C, "k": 2048, "fibre": kvl})}
    assert by["idx_scores"].densities is None


def test_v2_lite_extraction_unchanged():
    cfg = get_config("deepseek-v2-lite-16b")
    assert cfg.moe.first_dense == 0 and not cfg.mla.q_lora_rank
    names = [e.name for e in extract_network(cfg, "decode").matmuls]
    assert names[:6] == ["mla_q_proj", "mla_kv_a_proj", "mla_kv_b_proj",
                         "mla_o_proj", "attn_qk", "attn_av"]


def test_dedupe_keys_on_densities():
    sel = LayerMatmul("dsa_qk", 8, 576, 4096, param_instances=0,
                      select=("B", "n", 2048))
    plain = dataclasses.replace(sel, name="idx", select=None)
    unique, index = dedupe_shapes([sel, plain, sel])
    assert index == [0, 1, 0] and len(unique) == 2
    assert unique[0][:3] == unique[1][:3] == (8, 576, 4096)
    assert unique[0][3] == sel.densities and unique[1][3] is None


# ----------------------------------------------------------------------
# the selection density
# ----------------------------------------------------------------------

SELECTIONS = [(4096, 2048, 576), (131072, 2048, 576), (163840, 2048, 8),
              (512, 64, 3), (100, 99, 1)]


@pytest.mark.parametrize("L_,k,f", SELECTIONS)
def test_selected_scalar_matches_traced(L_, k, f):
    m = make_density_model(("selected", {"L": L_, "k": k, "fibre": f}),
                           L_ * f)
    assert isinstance(m, SelectedModel) and m.density == k / L_
    tiles = [1, f - 1, f, f + 1, 3 * f, SELECT_PRODUCT * f,
             (SELECT_PRODUCT + 1) * f, 100 * f, 1024 * f, L_ * f]
    with enable_x64():
        for t in filter(None, tiles):
            c = m.fibres(t)
            pe = float(m.prob_empty_b(t))
            want = m.prob_empty(t)
            # the exact product below SELECT_PRODUCT fibres; above, XLA's
            # gammaln and the C library's lgamma differ by ~1e-10 of the
            # L-sized arguments' logs
            tol = 1e-13 if c <= SELECT_PRODUCT else 1e-8
            assert pe == pytest.approx(want, rel=tol, abs=1e-300), (t, c)
            assert float(m.expected_density_b(t)) == k / L_
            assert float(m.max_nnz_b(t)) == m.max_nnz(t) == min(t, k * f)


def test_selected_against_monte_carlo():
    L_, k, f, trials = 512, 64, 3, 20000
    m = SelectedModel(L_ * f, L_, k, f)
    rng = np.random.default_rng(20251015)
    chosen = np.argsort(rng.random((trials, L_)), axis=1)[:, :k]
    picked = np.zeros((trials, L_), bool)
    np.put_along_axis(picked, chosen, True, axis=1)
    for c in (1, 4, 16, 80):
        # a tile of c whole fibres, at a random aligned place
        start = rng.integers(0, L_ // c, trials) * c
        cols = start[:, None] + np.arange(c)
        nnz = np.take_along_axis(picked, cols, axis=1).sum(1) * f
        p = m.prob_empty(c * f)
        sd = math.sqrt(p * (1 - p) / trials)
        assert abs((nnz == 0).mean() - p) <= 4 * sd + 1e-12, c
        assert nnz.max() <= m.max_nnz(c * f)
        assert nnz.mean() / (c * f) == pytest.approx(m.density, rel=0.05)


def test_selected_branch_pruned_without_selection():
    sel = SelectedModel(4096 * 8, 4096, 2048, 8)
    assert caps_for_models([sel]).select == 1
    assert caps_for_models(
        [make_density_model(("uniform", 0.3), 64)]).select == 0
    plain = TracedDensityStats(DensityCaps())
    assert len(plain._pe) == len(MODEL_KINDS) - 1
    assert len(TracedDensityStats(DensityCaps(select=1))._pe) \
        == len(MODEL_KINDS)

    def branches(caps):
        stats = TracedDensityStats(caps)
        jaxpr = jax.make_jaxpr(lambda i, p, t: stats.expected_density(
            i, p, jnp.zeros((3, 0)), t))(0, jnp.zeros(4), 8.0)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
        return len(eqn.params["branches"])

    assert branches(DensityCaps()) == 5
    assert branches(DensityCaps(select=1)) == 6


# ----------------------------------------------------------------------
# the row path and the sweep
# ----------------------------------------------------------------------

def _dsa_rows():
    rows = []
    for L_ in (4096, 131072):
        rows += [(8, 576, L_, {"B": ("selected", {
                     "rank": "n", "L": L_, "k": 2048, "fibre": 576})}),
                 (8, L_, 512, {"B": ("selected", {
                     "rank": "k", "L": L_, "k": 2048, "fibre": 512})})]
    return rows


@pytest.mark.parametrize("option", ["dense", "dsa-skip"])
def test_row_path_matches_scalar_on_selected_rows(option):
    opt = dsa_option()
    rows = _dsa_rows()
    for M, K, N, dens in rows:
        ((_, arg),) = dens.values()
        design = (dense_design(tpu_v5e_arch()) if option == "dense"
                  else opt.design_for(dens))
        assert option == "dense" or \
            design.name == tpu_dsa_design(arg["rank"]).name
        wl = matmul(M, K, N, densities=dens)
        nest = tpu_mapping(M, K, N)
        (got,) = Sparseloop(design).evaluate_rows([wl], [nest],
                                                  check_capacity=False)
        # the oracle keeps unit loops as loops; the row path drops them
        loops = dataclasses.replace(
            nest, loops=tuple(lp for lp in nest.loops if lp.bound > 1))
        ev = Sparseloop(design).evaluate(wl, loops, check_capacity=False)
        for k in ("cycles", "energy_pj", "edp"):
            assert float(got[k]) == pytest.approx(getattr(ev, k), rel=1e-9)


def test_dsv32_decode_sweep():
    from repro import obs
    tr = obs.enable()
    try:
        with compile_stats.track() as st:
            rep = fleet_sweep(["deepseek-v3.2-exp"], phases=("decode",),
                              seq_len=131072, ctx_grid=CTX_GRID)
    finally:
        obs.disable()
    assert st.compiles <= rep.compile_bound and st.scalar_evals == 0
    assert rep.option_names[-1] == "dsa-skip"
    selected = [r for r in rep.rows if r.densities]
    assert {r.layer for r in selected} == {"dsa_qk", "dsa_av"}
    for r in rep.rows:
        if r.densities:
            ((kind, arg),) = r.densities.values()
            assert kind == "selected" and arg["k"] / arg["L"] == 2048 / 131072
            assert set(r.options) == {"dense", "dsa-skip"}
        else:
            assert "dsa-skip" not in r.options
    assert set(rep.ctx_crossover) == {"dsa_qk:8x576xL", "dsa_av:8xLx512"}
    for per_opt in rep.ctx_crossover.values():
        assert set(per_opt) == {"dsa-skip"}
        assert per_opt["dsa-skip"] in CTX_GRID
    (span,) = tr.find("fleet.ctx_crossover")
    assert span.attrs["rows"] == 2 * len(CTX_GRID) * 2
    sel = {s.attrs["option"]: s.attrs["selected"]
           for s in tr.find("fleet.option")}
    assert sel == {"dense": 2, "nm-2:4": 0, "nm-2:8": 0, "dsa-skip": 2}
    # at the cell's cache both products gather the selected tokens:
    # each reads a few percent of what masked-dense attention does
    for r in selected:
        assert r.best_option == "dsa-skip"
        assert (r.options["dsa-skip"]["cycles"]
                < 0.2 * r.options["dense"]["cycles"])


def test_fleet6_rows_unchanged():
    """The six models of the fleet6 benchmark cell sweep to the rows the
    modeler gave before selections existed (recorded fixture)."""
    want = json.loads((DATA / "fleet6_rows.json").read_text())
    models = ["command-r-35b", "stablelm-1.6b", "whisper-base",
              "deepseek-v2-lite-16b", "xlstm-350m", "zamba2-7b"]
    with compile_stats.track() as st:
        rep = fleet_sweep(models, crossover=True,
                          crossover_grid=(8, 64, 512, 4096, 32768))
    got = rep.to_json()
    assert st.compiles <= rep.compile_bound == want["compile_bound"] == 3
    for row in got["rows"]:
        assert row.pop("densities") is None
    for key in ("rows", "crossover", "option_names", "unique_shapes",
                "total_entries", "total_dense_computes"):
        assert json.loads(json.dumps(got[key])) == want[key], key
    assert rep.ctx_crossover == {}


# ----------------------------------------------------------------------
# the absorbed chain against the substrate's forward
# ----------------------------------------------------------------------

def test_absorbed_chain_matches_substrate_forward():
    cfg = get_config("deepseek-v3.2-exp", reduced=True)
    m, x_, d, h = cfg.mla, cfg.dsa, cfg.d_model, cfg.num_heads
    B, S = 2, 24                    # cache of S, the last token decoded
    assert x_.index_topk < S        # the selection leaves tokens out
    by = {e.name: e for e in extract_network(
        cfg, "decode", batch=B, ctx_len=S).matmuls}
    p, _ = L.init_mla(cfg, jax.random.PRNGKey(7))
    xs = jax.random.normal(jax.random.PRNGKey(8), (B, S, d))
    pos = S - 1

    def mm(name, a, w):
        e = by[name]
        assert (a.shape[-2:], w.shape[-2:]) == ((e.M, e.K), (e.K, e.N))
        return a @ w

    with jax.default_matmul_precision("highest"):
        # the substrate: prefill S-1 tokens, then decode through the cache
        _, cache = L.mla_fwd(p, xs[:, :-1], cfg,
                             jnp.broadcast_to(jnp.arange(S - 1), (B, S - 1)))
        cache = tuple(jnp.pad(c, ((0, 0), (0, 1), (0, 0))) for c in cache)
        want, _ = L.mla_fwd(p, xs[:, -1:], cfg, None, cache=cache, pos=pos)

        # the chain: one row per sequence (T = B tokens)
        x = xs[:, -1]
        qpos = jnp.full((B, 1), pos)
        cq = L.apply_norm({"scale": p["q_norm"]},
                          mm("mla_q_a", x, p["w_dq"]))
        q = mm("mla_q_b", cq, p["w_uq"].reshape(m.q_lora_rank, -1)
               ).reshape(B, 1, h, -1)
        q_nope = q[..., :m.qk_nope_head_dim]
        q_rope = L.apply_rope(q[..., m.qk_nope_head_dim:], qpos,
                              cfg.rope_theta)[:, 0]
        allpos = jnp.broadcast_to(jnp.arange(S), (B, S))
        # each cached token's latent and indexer key came from its own
        # decode step's T-row products
        lat = jnp.stack([mm("mla_kv_a", xs[:, t], p["w_dkv"])
                         for t in range(S)], 1)
        c_kv = L.apply_norm({"scale": p["kv_norm"]}, lat[..., :m.kv_lora_rank])
        k_rope = L.apply_rope(lat[..., None, m.kv_lora_rank:], allpos,
                              cfg.rope_theta)[..., 0, :]
        latent = jnp.concatenate([c_kv, k_rope], -1)       # (B, S, 576)
        # absorb W_UK into the query, per head
        q_c = jnp.stack([mm("mla_uk", q_nope[:, 0, i], p["w_uk"][:, i].T)
                         for i in range(h)], 1)            # (B, h, r)
        q_lat = jnp.concatenate([q_c, q_rope], -1)         # (B, h, 576)
        # the indexer over the whole cache
        qi = mm("idx_q", cq, p["idx_wq"].reshape(cq.shape[-1], -1)
                ).reshape(B, 1, x_.index_n_heads, -1)
        qi = L._idx_rope(qi, qpos, cfg)[:, 0]
        ki = L.apply_norm(p["idx_k_norm"], jnp.stack(
            [mm("idx_k", xs[:, t], p["idx_wk"]) for t in range(S)], 1))
        ki = L._idx_rope(ki[..., None, :], allpos, cfg)[..., 0, :]
        w = mm("idx_w", x, p["idx_ww"]) * (
            (x_.index_n_heads * x_.index_head_dim) ** -0.5)
        ctxs = []
        for b in range(B):
            scores = mm("idx_scores", qi[b], ki[b].T)      # (ih, S)
            index = w[b] @ jax.nn.relu(scores)
            sel = jax.lax.top_k(index, x_.index_topk)[1]
            # the selected tokens only: the products' k of L
            s = mm("dsa_qk", q_lat[b], latent[b].T)[:, sel]
            s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
            prob = jax.nn.softmax(s, axis=-1)
            full = jnp.zeros((h, S)).at[:, sel].set(prob)
            ctxs.append(mm("dsa_av", full, c_kv[b]))      # (h, r)
        ctx = jnp.stack(ctxs)                              # (B, h, r)
        # W_UV out of the latent, per head, then the output projection
        o = jnp.concatenate([mm("mla_uv", ctx[:, i], p["w_uv"][:, i])
                             for i in range(h)], -1)
        got = mm("mla_o", o, p["wo"])[:, None]
    # float32 at full matmul precision: the two differ only in the order
    # of their sums (gathered against masked) and in where the head
    # split falls, ~1e-6; bfloat16 inputs would read ~1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(want).max()))


def test_dsa_decode_through_cache_matches_prefill():
    from repro.models import get_api
    cfg = get_config("deepseek-v3.2-exp", reduced=True)
    # room in every expert for every token: the capacity drops a batch's
    # overflow, so a 42-token prefill and a 2-token step would otherwise
    # route differently
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(3))
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 21), 0,
                             cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        _, cache = api.prefill(params, tok[:, :20], cfg, 24)
        step, _ = api.decode_step(params, tok[:, 20:], cache, 20, cfg)
        full, _ = api.prefill(params, tok, cfg, 24)
    # the same float32 forward along two paths: they differ in the order
    # of their sums only (~3e-6 here)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
