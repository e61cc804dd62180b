"""Fleet extraction / sweep / validation contracts.

The load-bearing claims, each pinned exactly:

* **parameter exactness** — the extraction walk reproduces
  ``ModelConfig.param_count()`` to the parameter for every CONFIG and
  REDUCED config (all 10 families: GQA, MLA+MoE, SSM/xLSTM, Mamba2
  hybrid, encoder-decoder);
* **FLOP exactness** — ``total_flops`` matches independent closed-form
  per-family formulas for prefill AND decode;
* **merge/dedup** — identical layers collapse at extraction
  (count=num_layers) and identical shapes collapse at evaluation,
  with the avoided work counted in ``compile_stats.dedup_evals``;
* **production sharding** — per-device shapes under the 16x16 mesh
  match hand-computed Megatron-style splits, and indivisible axes
  replicate instead of going fractional;
* **compile accounting** — a REDUCED sweep stays within its structural
  compile bound with zero scalar-path evaluations, and the batched
  results match the scalar reference oracle;
* **validation arms** — the deterministic (no wall-clock) arms of the
  kernel-agreement harness pass: N:M packed-bytes traffic sign and
  kernel correctness.
"""
import dataclasses

import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.core import compile_stats
from repro.core.advisor import LayerAdvice, advise, tpu_mapping
from repro.core.engine import Sparseloop
from repro.core.workload import matmul
from repro.fleet.extract import (MeshSpec, extract_network,
                                 production_mesh_spec, shard_entries)
from repro.fleet.sweep import (WIN_MARGIN, compile_bound, dedupe_shapes,
                               default_options, fleet_sweep)
from repro.fleet.validate import (DETERMINISTIC_ARMS, kernel_cell,
                                  validate_fleet)
from repro.launch.mesh import production_mesh_shape

ALL_CONFIGS = [(name, reduced) for name in ARCH_NAMES
               for reduced in (False, True)]


# ----------------------------------------------------------------------
# parameter exactness (every family, every config)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name,reduced", ALL_CONFIGS,
                         ids=[f"{n}{'-reduced' if r else ''}"
                              for n, r in ALL_CONFIGS])
def test_param_exactness(name, reduced):
    cfg = get_config(name, reduced=reduced)
    net = extract_network(cfg, "prefill", seq_len=32, batch=2)
    assert net.total_params == cfg.param_count(), (
        f"{cfg.name}: extracted {net.total_params} params, "
        f"param_count() says {cfg.param_count()}")


def test_decode_touches_all_decoder_weights():
    # decode runs the same weight matmuls (encoder-side weights excluded
    # for enc_dec models, which only run the encoder at prefill)
    cfg = get_config("qwen3-4b")
    pre = extract_network(cfg, "prefill", seq_len=32, batch=2)
    dec = extract_network(cfg, "decode", batch=4)
    assert dec.total_params == pre.total_params == cfg.param_count()


# ----------------------------------------------------------------------
# FLOP exactness (closed forms per family)
# ----------------------------------------------------------------------

def test_flops_gqa_prefill_and_decode():
    cfg = get_config("qwen3-4b")
    L, d, H, kv, hd = (cfg.num_layers, cfg.d_model, cfg.num_heads,
                       cfg.num_kv_heads, cfg.head_dim)
    dff, v = cfg.d_ff, cfg.vocab_size
    S, B = 128, 2
    T = S * B
    weights = 2 * T * (L * (d * (H + 2 * kv) * hd     # qkv
                            + H * hd * d              # o_proj
                            + d * 2 * dff + dff * d)  # gated FFN
                       + d * v)                       # lm head
    attn = L * H * B * (2 * S * hd * S + 2 * S * S * hd)
    net = extract_network(cfg, "prefill", seq_len=S, batch=B)
    assert net.total_flops == weights + attn

    C = 512
    dec = extract_network(cfg, "decode", batch=B, ctx_len=C)
    dweights = 2 * B * (L * (d * (H + 2 * kv) * hd + H * hd * d
                             + d * 2 * dff + dff * d) + d * v)
    dattn = L * H * B * (2 * 1 * hd * C + 2 * 1 * C * hd)
    assert dec.total_flops == dweights + dattn


def test_flops_mla_moe():
    cfg = get_config("deepseek-v2-lite-16b", reduced=True)
    m, e = cfg.mla, cfg.moe
    d, v, L, H = cfg.d_model, cfg.vocab_size, cfg.num_layers, cfg.num_heads
    S, B = 64, 2
    T = S * B                       # T*top_k % num_experts == 0: exact
    assert (T * e.top_k) % e.num_experts == 0
    tok = (T * e.top_k) // e.num_experts
    qk, vd = m.qk_nope_head_dim + m.qk_rope_head_dim, m.v_head_dim
    expect = 0
    for layer in range(L):
        expect += 2 * T * (d * H * qk                       # q_proj
                           + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                           + m.kv_lora_rank * H * (m.qk_nope_head_dim
                                                   + vd)
                           + H * vd * d)                    # o_proj
        expect += H * B * (2 * S * qk * S + 2 * S * S * vd)
        if cfg.is_moe_layer(layer):
            expect += 2 * T * d * e.num_experts             # router
            expect += e.num_experts * 2 * tok * (d * 2 * e.expert_d_ff
                                                 + e.expert_d_ff * d)
            expect += e.num_shared_experts * 2 * T * (
                d * 2 * e.shared_d_ff + e.shared_d_ff * d)
        else:
            expect += 2 * T * (d * 2 * cfg.d_ff + cfg.d_ff * d)
    expect += 2 * T * d * v
    net = extract_network(cfg, "prefill", seq_len=S, batch=B)
    assert net.total_flops == expect


def test_flops_xlstm():
    cfg = get_config("xlstm-350m")
    d, di = cfg.d_model, cfg.ssm_expand * cfg.d_model
    S, B = 32, 4
    T = S * B
    # each block: up (d -> 2di) + down (di -> d); no FFN, no attention
    expect = cfg.num_layers * (2 * T * d * 2 * di + 2 * T * di * d) \
        + 2 * T * d * cfg.vocab_size
    net = extract_network(cfg, "prefill", seq_len=S, batch=B)
    assert net.total_flops == expect
    assert net.attention_matmuls() == ()


def test_flops_hybrid_shared_attn():
    cfg = get_config("zamba2-7b", reduced=True)
    d, di = cfg.d_model, cfg.ssm_expand * cfg.d_model
    L, H, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    sd = cfg.hybrid.shared_attn_d_ff
    apps = L // cfg.hybrid.period
    S, B = 16, 2
    T = S * B
    per_mamba = (2 * T * d * 2 * di + 2 * T * di * d
                 + 2 * T * di * (2 * cfg.ssm_state + 3)
                 + 2 * T * (d * 2 * cfg.d_ff + cfg.d_ff * d))
    shared = apps * (2 * T * (d * (cfg.q_dim + 2 * cfg.kv_dim)
                              + cfg.q_dim * d + d * 2 * sd + sd * d)
                     + H * B * (2 * S * hd * S + 2 * S * S * hd))
    expect = L * per_mamba + shared + 2 * T * d * cfg.vocab_size
    net = extract_network(cfg, "prefill", seq_len=S, batch=B)
    assert net.total_flops == expect
    # the shared block's weights materialize ONCE (not per application)
    qkv = next(e for e in net.matmuls if e.name == "shared_attn_qkv")
    assert qkv.count == apps and qkv.param_instances == 1


def test_flops_enc_dec():
    cfg = get_config("whisper-base", reduced=True)
    d, dff, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    L, EL, H, hd = (cfg.num_layers, cfg.enc_layers, cfg.num_heads,
                    cfg.head_dim)
    S, B, E = 64, 2, 8              # S > dec_max_len=32: clamps
    DS = min(S, cfg.dec_max_len)
    T, Te = DS * B, E * B
    dec_self = L * (2 * T * (d * (cfg.q_dim + 2 * cfg.kv_dim)
                             + cfg.q_dim * d + d * 2 * dff + dff * d)
                    + H * B * (2 * DS * hd * DS + 2 * DS * DS * hd))
    enc = EL * (2 * Te * (d * 3 * d + d * d + d * 2 * dff + dff * d)
                + H * B * (2 * E * hd * E + 2 * E * E * hd))
    cross = L * (2 * Te * (d * d + d * d)         # cached K/V
                 + 2 * T * (d * d + d * d)        # per-step Q/O
                 + H * B * (2 * DS * hd * E + 2 * DS * E * hd))
    expect = dec_self + enc + cross + 2 * T * d * v
    net = extract_network(cfg, "prefill", seq_len=S, batch=B, enc_len=E)
    assert net.total_flops == expect
    # decode drops the encoder + cached cross-K/V, keeps per-step Q/O
    dec = extract_network(cfg, "decode", batch=B, enc_len=E)
    names = {e.name for e in dec.matmuls}
    assert "enc_qkv" not in names and "cross_k_proj" not in names
    assert "cross_q_proj" in names and "cross_attn_qk" in names


# ----------------------------------------------------------------------
# merge + dedup
# ----------------------------------------------------------------------

def test_identical_layers_merge():
    cfg = get_config("qwen3-4b")
    net = extract_network(cfg, "prefill", seq_len=32, batch=2)
    qkv = [e for e in net.matmuls if e.name == "attn_qkv"]
    assert len(qkv) == 1
    assert qkv[0].count == cfg.num_layers
    assert qkv[0].param_instances == cfg.num_layers
    assert qkv[0].weight_params == (qkv[0].K * qkv[0].N
                                    * cfg.num_layers)


def test_dedupe_shapes_fanout():
    from repro.fleet.extract import LayerMatmul
    entries = [LayerMatmul("a", 8, 16, 32), LayerMatmul("b", 8, 16, 64),
               LayerMatmul("c", 8, 16, 32), LayerMatmul("d", 8, 16, 32)]
    unique, index = dedupe_shapes(entries)
    assert len(unique) == 2
    assert [unique[i] for i in index] == [e.shape for e in entries]


def test_dedup_evals_counter():
    with compile_stats.track() as st:
        compile_stats.record_dedup_evals(7)
    assert st.dedup_evals == 7
    delta = st - compile_stats.CompileStats(dedup_evals=3)
    assert delta.dedup_evals == 4
    assert st.copy().dedup_evals == 7


# ----------------------------------------------------------------------
# production sharding
# ----------------------------------------------------------------------

def test_production_mesh_spec_matches_launch():
    spec = production_mesh_spec()
    assert spec.axes == production_mesh_shape()
    assert spec.size == 256
    assert spec.axis_names == ("data", "model")
    assert production_mesh_spec(multi_pod=True).size == 512


def test_production_shard_command_r():
    cfg = get_config("command-r-35b")
    mesh = production_mesh_spec()
    net = shard_entries(extract_network(cfg, "prefill"), mesh)
    by = {e.name: e for e in net.matmuls}
    # T = 4096*16 over data=16 -> M 4096; qkv N = (64+16)*128 = 10240
    # over model=16 -> 640; o_proj K = 8192 over model -> 512
    assert by["attn_qkv"].shape == (4096, 8192, 640)
    assert by["attn_qkv"].count == cfg.num_layers == 40
    assert by["attn_o_proj"].shape == (4096, 512, 8192)
    # attention score count = 64 heads * 16 seqs * 40 layers = 40960,
    # heads sharded on model (16) then sequences on data (16) -> 160
    assert by["attn_qk"].count == 160
    dec = shard_entries(extract_network(cfg, "decode"), mesh)
    assert {e.name: e for e in dec.matmuls}["attn_qkv"].M == 256 // 16


def test_indivisible_axes_replicate():
    cfg = get_config("qwen3-4b")
    mesh = MeshSpec((("data", 3), ("model", 7)))
    net = shard_entries(
        extract_network(cfg, "prefill", seq_len=9, batch=2), mesh)
    by = {e.name: e for e in net.matmuls}
    assert by["attn_qkv"].M == 6              # 18 tokens / data=3
    # N = (32+16)*128 = 6144, not divisible by 7 -> replicated
    assert by["attn_qkv"].N == 6144
    assert by["ffn_down"].K == cfg.d_ff       # 9728 % 7 != 0


# ----------------------------------------------------------------------
# sweep: compile accounting + scalar parity + verdicts
# ----------------------------------------------------------------------

def test_reduced_sweep_compile_accounting():
    # the same config listed twice guarantees cross-network duplicate
    # shapes, so dedup must fire
    names = ("qwen3-4b", "qwen3-4b")
    with compile_stats.track() as st:
        rep = fleet_sweep(names, reduced=True, seq_len=32, batch=2)
    assert st.compiles <= rep.compile_bound
    assert rep.compile_bound == len(rep.option_names)
    assert st.scalar_evals == 0
    assert st.dedup_evals > 0
    assert rep.total_entries == len(rep.rows)
    assert rep.unique_shapes <= rep.total_entries
    for r in rep.rows:
        assert r.verdict in ("compress", "dense")
        assert r.options["dense"]["cycles"] == r.dense_cycles
        if r.verdict == "compress":
            assert r.best_cycles * WIN_MARGIN < r.dense_cycles
        assert r.speedup >= 1.0


def test_sweep_matches_scalar_oracle():
    # one weight shape through the fleet path vs the scalar reference
    opt = default_options(((2, 4),))
    rep = fleet_sweep(("qwen3-4b",), reduced=True, phases=("decode",),
                      nm_options=((2, 4),), mesh=None, batch=16)
    dense_engine = Sparseloop(opt[0].design)
    nm_engine = Sparseloop(opt[1].design)
    for r in rep.rows:
        if r.layer != "lm_head":
            continue
        wl = matmul(r.M, r.K, r.N)
        ev = dense_engine.evaluate(wl, tpu_mapping(r.M, r.K, r.N),
                                   check_capacity=False)
        assert r.dense_cycles == pytest.approx(ev.cycles, rel=1e-6)
        wl_nm = matmul(r.M, r.K, r.N, densities=opt[1].densities)
        ev_nm = nm_engine.evaluate(wl_nm, tpu_mapping(r.M, r.K, r.N),
                                   check_capacity=False)
        assert r.options["nm-2:4"]["cycles"] == pytest.approx(
            ev_nm.cycles, rel=1e-6)
        break
    else:
        pytest.fail("lm_head row missing")


def test_compile_bound_is_layer_count_independent():
    opts = default_options()
    few = extract_network(get_config("qwen3-4b", reduced=True),
                          "prefill", seq_len=16, batch=1).matmuls
    many = [e for name in ARCH_NAMES[:4] for e in extract_network(
        get_config(name, reduced=True), "prefill", seq_len=16,
        batch=1).matmuls]
    assert (compile_bound(opts, few) == compile_bound(opts, many)
            == len(opts))


def test_crossover_values_on_grid():
    grid = (8, 64, 512)
    rep = fleet_sweep(("qwen3-4b",), reduced=True, phases=("decode",),
                      batch=16, crossover=True, crossover_grid=grid)
    assert rep.crossover
    for kn, per_opt in rep.crossover.items():
        K, N = map(int, kn.split("x"))
        assert K > 0 and N > 0
        for opt, last_win in per_opt.items():
            assert opt in rep.option_names
            assert last_win is None or last_win in grid


# ----------------------------------------------------------------------
# advisor back-compat + validation (deterministic arms only)
# ----------------------------------------------------------------------

def test_advise_backcompat():
    cfg = get_config("qwen3-4b")
    with compile_stats.track() as st:
        adv = advise(cfg, tokens_per_device=8, tp=16)
    assert adv and all(isinstance(a, LayerAdvice) for a in adv)
    # N:M keeps n/m of the weights plus coordinate overhead, so an
    # HBM-bound matmul's speedup is bounded by the inverse byte ratio:
    # 2:4 -> 1/0.5625, 2:8 -> 1/(0.25 * (1 + 3/32))
    bound = {"dense": 1.0, "nm-2:4": 1.0 / 0.5625,
             "nm-2:8": 1.0 / (0.25 * (1 + 3 / 32))}
    for a in adv:
        assert a.dense_bottleneck in ("compute", "HBM")
        assert a.best_name in bound
        assert 1.0 <= a.speedup <= bound[a.best_name] + 0.01
    assert st.scalar_evals == 0
    names = {a.layer for a in adv}
    assert {"attn_qkv", "ffn_gate_up", "lm_head"} <= names


def test_kernel_cell_padding():
    assert kernel_cell(8, 544, 300) == (8, 640, 512)
    assert kernel_cell(8, 544, 300, bs=64) == (8, 576, 512)
    assert kernel_cell(1000, 512, 512) == (128, 512, 512)
    assert kernel_cell(3, 100, 100, bs=64, min_dim=128) == (8, 128, 128)


def test_validate_deterministic_arms():
    rows = validate_fleet(("qwen3-4b", "xlstm-350m"),
                          arms=DETERMINISTIC_ARMS, reps=1,
                          min_dim=128, max_cells_per_config=1)
    assert rows
    assert {r.arm for r in rows} == set(DETERMINISTIC_ARMS)
    bad = [r for r in rows if not r.agree]
    assert not bad, [dataclasses.asdict(r) for r in bad]
    for r in rows:
        if r.arm == "nm-correct":
            assert r.measured < 1e-3
        if r.arm == "nm-traffic":
            # 2:4 f32 packs to ~0.53x the dense bytes
            assert r.measured > 1.5
