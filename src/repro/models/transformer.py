"""Model assembly for all 10 assigned architectures.

Families and their layer-stack execution strategies (all scan-based so the
compiled HLO contains ONE block body per group — compile time stays flat
in depth, which is what makes the 80-cell dry-run tractable):

  * dense / moe / vlm : one `lax.scan` over L stacked decoder blocks
  * ssm (xlstm)       : scan over L/2 stacked (mLSTM, sLSTM) pairs
  * hybrid (zamba2)   : scan over super-blocks of `period` Mamba2 layers
                        followed by ONE SHARED attention block (parameters
                        shared across applications — the zamba trick)
  * audio (whisper)   : encoder scan (non-causal) + decoder scan with
                        self- and cross-attention; frontend is a stub that
                        consumes precomputed frame embeddings

Each family provides: init, forward_hidden (training), prefill,
decode_step, init_cache.  Params are nested dicts; every init returns a
matching PartitionSpec tree.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from . import layers as L
from . import ssm as S
from .layers import MODEL, DATA


def _vmap_init(init_fn, n: int, key):
    """Stack n independent inits along a leading axis; spec gains a
    leading None."""
    keys = jax.random.split(key, n)
    params0, spec = init_fn(keys[0])
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    spec = jax.tree.map(lambda s: P(None, *s), spec,
                        is_leaf=lambda x: isinstance(x, P))
    return params, spec


def _f32_to(params, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, params)


# ======================================================================
# Decoder block (attn/mla + mlp/moe) used by dense/moe/vlm + whisper dec
# ======================================================================
def init_block(cfg: ModelConfig, key, *, cross: bool = False,
               moe_layer: bool | None = None):
    ks = jax.random.split(key, 6)
    moe_layer = cfg.moe is not None if moe_layer is None else moe_layer
    p, sp = {}, {}
    p["ln1"], sp["ln1"] = L.init_norm(cfg, cfg.d_model)
    if cfg.mla:
        p["attn"], sp["attn"] = L.init_mla(cfg, ks[0])
    else:
        p["attn"], sp["attn"] = L.init_attention(cfg, ks[0])
    if cross:
        p["ln_x"], sp["ln_x"] = L.init_norm(cfg, cfg.d_model)
        p["xattn"], sp["xattn"] = L.init_attention(cfg, ks[1])
    if not cfg.parallel_block:
        p["ln2"], sp["ln2"] = L.init_norm(cfg, cfg.d_model)
    if moe_layer:
        p["moe"], sp["moe"] = L.init_moe(cfg, ks[2])
    else:
        p["mlp"], sp["mlp"] = L.init_mlp(cfg.d_model, cfg.d_ff, ks[2])
    if cfg.parallel_block and cfg.fused_proj and not moe_layer:
        # PaLM-style fusion: [attn_heads ; ffn_hidden] @ W_fused — the
        # two model-sharded contractions become ONE (one all-reduce).
        # The separate wo matrices are dropped.
        del p["attn"]["wo"], sp["attn"]["wo"]
        del p["mlp"]["wo"], sp["mlp"]["wo"]
        from jax.sharding import PartitionSpec as P
        p["w_fused"] = L._init(jax.random.fold_in(key, 7),
                               (cfg.q_dim + cfg.d_ff, cfg.d_model))
        sp["w_fused"] = P(L.MODEL, None)
    return p, sp


def block_fwd(p, x, cfg: ModelConfig, positions, *, mode="train",
              cache=None, pos=None, enc_kv=None):
    """mode: train | prefill | decode.  Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["ln1"], x)
    if "w_fused" in p:
        # fused parallel block: one model-sharded contraction for both
        # attention and FFN outputs -> one all-reduce per layer
        if mode == "train":
            o = L.attention_fwd(p["attn"], h, cfg, positions,
                                project=False)
            new_cache = None
        elif mode == "prefill":
            o, new_cache = L.attention_prefill(p["attn"], h, cfg,
                                               positions, project=False)
        else:
            o, new_cache = L.attention_decode(p["attn"], h, cache, cfg,
                                              pos, project=False)
        hid = L.mlp_hidden(p["mlp"], h)
        fused = jnp.concatenate([o, hid], axis=-1) \
            @ p["w_fused"].astype(x.dtype)
        return x + fused, new_cache, aux
    if cfg.mla:
        if mode == "train":
            a, new_cache = L.mla_fwd(p["attn"], h, cfg, positions)
            new_cache = None
        elif mode == "prefill":
            a, new_cache = L.mla_fwd(p["attn"], h, cfg, positions)
        else:
            a, new_cache = L.mla_fwd(p["attn"], h, cfg, positions,
                                     cache=cache, pos=pos)
    else:
        if mode == "train":
            a = L.attention_fwd(p["attn"], h, cfg, positions)
            new_cache = None
        elif mode == "prefill":
            a, new_cache = L.attention_prefill(p["attn"], h, cfg, positions)
        else:
            a, new_cache = L.attention_decode(p["attn"], h, cache, cfg, pos)

    if cfg.parallel_block:
        # command-r: attention and FFN read the same norm, summed
        if "moe" in p:
            f, aux = L.moe_fwd(p["moe"], h, cfg)
        else:
            f = L.mlp_fwd(p["mlp"], h)
        x = x + a + f
    else:
        x = x + a
        if enc_kv is not None:
            hx = L.apply_norm(p["ln_x"], x)
            x = x + L.cross_attention_fwd(p["xattn"], hx, enc_kv, cfg)
        h2 = L.apply_norm(p["ln2"], x)
        if "moe" in p:
            f, aux = L.moe_fwd(p["moe"], h2, cfg)
        else:
            f = L.mlp_fwd(p["mlp"], h2)
        x = x + f
    return x, new_cache, aux


# ======================================================================
# Family: dense / moe / vlm decoder-only LM
# ======================================================================
def init_lm(cfg: ModelConfig, key):
    ks = jax.random.split(key, 4)
    p, sp = {}, {}
    p["embed"], sp["embed"] = L.init_embedding(cfg, ks[0])
    first = cfg.moe.first_dense if cfg.moe else 0
    if first:
        p["dense_blocks"], sp["dense_blocks"] = _vmap_init(
            lambda k: init_block(cfg, k, moe_layer=False), first, ks[3])
    p["blocks"], sp["blocks"] = _vmap_init(
        lambda k: init_block(cfg, k), cfg.num_layers - first, ks[1])
    p["ln_f"], sp["ln_f"] = L.init_norm(cfg, cfg.d_model)
    return _f32_to(p, jnp.dtype(cfg.dtype)), sp


def _stacks(params) -> list:
    """The decoder's stacked blocks in layer order: a MoE model's leading
    dense-FFN blocks (``MoEConfig.first_dense``), then the rest."""
    return [params[k] for k in ("dense_blocks", "blocks") if k in params]


REMAT_POLICIES = {
    "full": None,   # recompute everything in the backward pass
    # save weight-contraction results: the backward pass does not replay
    # the forward matmuls NOR their TP all-reduces
    "dots": "dots_with_no_batch_dims_saveable",
}


def _remat(body, policy: str | None):
    if policy is None:
        return body
    name = REMAT_POLICIES.get(policy, None)
    pol = getattr(jax.checkpoint_policies, name) if name else None
    return jax.checkpoint(body, prevent_cse=False, policy=pol)


def lm_hidden(params, x, cfg: ModelConfig, positions, *, remat=True,
              remat_policy: str = "full"):
    def body(carry, bp):
        h, aux = carry
        h, _, a = block_fwd(bp, h, cfg, positions, mode="train")
        return (h, aux + a), None

    if remat:
        body = _remat(body, remat_policy)
    carry = (x, jnp.zeros((), jnp.float32))
    for stack in _stacks(params):
        carry, _ = jax.lax.scan(body, carry, stack)
    x, aux = carry
    return L.apply_norm(params["ln_f"], x), aux


def lm_forward_train(params, tokens, cfg: ModelConfig, *, remat=True,
                     prefix_embeds=None, remat_policy: str = "full"):
    B, Stok = tokens.shape
    x = L.embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:   # vlm: precomputed patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), (B, x.shape[1]))
    return lm_hidden(params, x, cfg, positions, remat=remat,
                     remat_policy=remat_policy)


def lm_init_cache(cfg: ModelConfig, B: int, S: int, dtype):
    Lh = cfg.num_layers
    if cfg.mla:
        m = cfg.mla
        widths = (m.kv_lora_rank, m.qk_rope_head_dim) + (
            (cfg.dsa.index_head_dim,) if cfg.dsa else ())
        return tuple(jnp.zeros((Lh, B, S, w), dtype) for w in widths)
    return (jnp.zeros((Lh, B, S, cfg.num_kv_heads, cfg.head_dim), dtype),
            jnp.zeros((Lh, B, S, cfg.num_kv_heads, cfg.head_dim), dtype))


def cache_specs(cfg: ModelConfig):
    """PartitionSpecs for the KV cache (kv heads->model, batch->data)."""
    if cfg.mla:
        return (P(None, DATA, None, None),) * (3 if cfg.dsa else 2)
    return (P(None, DATA, None, MODEL, None),
            P(None, DATA, None, MODEL, None))


def lm_prefill(params, tokens, cfg: ModelConfig, S_max: int,
               prefix_embeds=None):
    B = tokens.shape[0]
    x = L.embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    Sx = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Sx), (B, Sx))

    def body(carry, bp):
        h = carry
        h, kv, _ = block_fwd(bp, h, cfg, positions, mode="prefill")
        return h, kv

    per_stack = []
    for stack in _stacks(params):
        x, kv = jax.lax.scan(body, x, stack)
        per_stack.append(kv)
    kvs = [jnp.concatenate(parts) for parts in zip(*per_stack)]
    x = L.apply_norm(params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x[:, -1:, :], cfg)
    # place into S_max-sized cache
    cache = tuple(jax.lax.dynamic_update_slice(c0, kv, (0,) * c0.ndim)
                  for c0, kv in zip(lm_init_cache(cfg, B, S_max, x.dtype),
                                    kvs))
    return logits, cache


def lm_decode_step(params, token, cache, pos, cfg: ModelConfig):
    """token: (B,1) int32; cache: stacked over layers; pos: scalar."""
    x = L.embed(params["embed"], token, cfg)

    def body(h, xs):
        bp, c = xs
        h, new_c, _ = block_fwd(bp, h, cfg, None, mode="decode",
                                cache=c, pos=pos)
        return h, new_c

    new_parts, at = [], 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        x, part = jax.lax.scan(
            body, x, (stack, tuple(c[at:at + n] for c in cache)))
        new_parts.append(part)
        at += n
    new_cache = tuple(jnp.concatenate(parts) for parts in zip(*new_parts))
    x = L.apply_norm(params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg), new_cache


# ======================================================================
# Family: ssm (xLSTM) — alternating mLSTM/sLSTM pairs
# ======================================================================
def _xlstm_pair_init(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    p, sp = {}, {}
    p["ln_m"], sp["ln_m"] = L.init_norm(cfg, cfg.d_model)
    p["mlstm"], sp["mlstm"] = S.init_mlstm(cfg, k1)
    p["ln_s"], sp["ln_s"] = L.init_norm(cfg, cfg.d_model)
    p["slstm"], sp["slstm"] = S.init_slstm(cfg, k2)
    return p, sp


def init_xlstm(cfg: ModelConfig, key):
    ks = jax.random.split(key, 3)
    n_pairs = cfg.num_layers // 2
    p, sp = {}, {}
    p["embed"], sp["embed"] = L.init_embedding(cfg, ks[0])
    p["pairs"], sp["pairs"] = _vmap_init(
        lambda k: _xlstm_pair_init(cfg, k), n_pairs, ks[1])
    p["ln_f"], sp["ln_f"] = L.init_norm(cfg, cfg.d_model)
    return _f32_to(p, jnp.dtype(cfg.dtype)), sp


def _xlstm_pair_fwd(bp, x, cfg, state=None):
    st_m = None if state is None else state[0]
    st_s = None if state is None else state[1]
    y, new_m = S.mlstm_fwd(bp["mlstm"], L.apply_norm(bp["ln_m"], x),
                           cfg, st_m)
    x = x + y
    y, new_s = S.slstm_fwd(bp["slstm"], L.apply_norm(bp["ln_s"], x),
                           cfg, st_s)
    return x + y, (new_m, new_s)


def xlstm_hidden(params, x, cfg: ModelConfig, *, remat=True):
    def body(h, bp):
        h, _ = _xlstm_pair_fwd(bp, h, cfg)
        return h, None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["pairs"])
    return L.apply_norm(params["ln_f"], x), jnp.zeros((), jnp.float32)


def xlstm_forward_train(params, tokens, cfg, *, remat=True,
                        prefix_embeds=None):
    x = L.embed(params["embed"], tokens, cfg)
    return xlstm_hidden(params, x, cfg, remat=remat)


def xlstm_init_state(cfg: ModelConfig, B: int, dtype):
    n_pairs = cfg.num_layers // 2
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H, hd = cfg.num_heads, (cfg.ssm_expand * d) // cfg.num_heads
    Kc = cfg.ssm_conv - 1
    m_state = (jnp.zeros((n_pairs, B, Kc, d_in), dtype),
               jnp.zeros((n_pairs, B, H, hd + 1, hd), dtype))
    s_state = (jnp.zeros((n_pairs, B, d), dtype),
               jnp.zeros((n_pairs, B, d), jnp.float32),
               jnp.ones((n_pairs, B, d), jnp.float32),
               jnp.zeros((n_pairs, B, d), jnp.float32))
    return (m_state, s_state)


def xlstm_prefill(params, tokens, cfg: ModelConfig, S_max: int):
    x = L.embed(params["embed"], tokens, cfg)

    def body(h, bp):
        h, st = _xlstm_pair_fwd(bp, h, cfg)
        return h, st

    x, states = jax.lax.scan(body, x, params["pairs"])
    x = L.apply_norm(params["ln_f"], x)
    return L.lm_logits(params["embed"], x[:, -1:, :], cfg), states


def xlstm_decode_step(params, token, state, pos, cfg: ModelConfig):
    x = L.embed(params["embed"], token, cfg)

    def body(h, xs):
        bp, st = xs
        h, new_st = _xlstm_pair_fwd(bp, h, cfg, state=st)
        return h, new_st

    x, new_state = jax.lax.scan(body, x, (params["pairs"], state))
    x = L.apply_norm(params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg), new_state


# ======================================================================
# Family: hybrid (zamba2) — Mamba2 super-blocks + one shared attn block
# ======================================================================
def init_hybrid(cfg: ModelConfig, key):
    ks = jax.random.split(key, 5)
    period = cfg.hybrid.period
    n_super = cfg.num_layers // period
    p, sp = {}, {}
    p["embed"], sp["embed"] = L.init_embedding(cfg, ks[0])

    def mamba_block_init(k):
        bp, bs = {}, {}
        bp["ln"], bs["ln"] = L.init_norm(cfg, cfg.d_model)
        bp["mamba"], bs["mamba"] = S.init_mamba2(cfg, k)
        return bp, bs

    p["mamba"], sp["mamba"] = _vmap_init(
        mamba_block_init, n_super * period, ks[1])
    # ONE shared attention block (params reused at every application)
    shared_cfg = dataclasses.replace(
        cfg, d_ff=cfg.hybrid.shared_attn_d_ff or cfg.d_ff, moe=None,
        mla=None)
    p["shared"], sp["shared"] = init_block(shared_cfg, ks[2],
                                           moe_layer=False)
    p["ln_f"], sp["ln_f"] = L.init_norm(cfg, cfg.d_model)
    return _f32_to(p, jnp.dtype(cfg.dtype)), sp


def _hybrid_shared_cfg(cfg):
    return dataclasses.replace(
        cfg, d_ff=cfg.hybrid.shared_attn_d_ff or cfg.d_ff, moe=None,
        mla=None)


def hybrid_hidden(params, x, cfg: ModelConfig, positions, *, remat=True):
    period = cfg.hybrid.period
    n_super = cfg.num_layers // period
    B = x.shape[0]
    scfg = _hybrid_shared_cfg(cfg)
    mamba = jax.tree.map(
        lambda a: a.reshape(n_super, period, *a.shape[1:]), params["mamba"])

    def super_body(h, bp):
        def inner(h2, ip):
            y, _ = S.mamba2_fwd(ip["mamba"],
                                L.apply_norm(ip["ln"], h2), cfg)
            return h2 + y, None
        h, _ = jax.lax.scan(inner, h, bp)
        h, _, _ = block_fwd(params["shared"], h, scfg, positions,
                            mode="train")
        return h, None

    if remat:
        super_body = jax.checkpoint(super_body, prevent_cse=False)
    x, _ = jax.lax.scan(super_body, x, mamba)
    return L.apply_norm(params["ln_f"], x), jnp.zeros((), jnp.float32)


def hybrid_forward_train(params, tokens, cfg, *, remat=True,
                         prefix_embeds=None):
    B, Stok = tokens.shape
    x = L.embed(params["embed"], tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(Stok), (B, Stok))
    return hybrid_hidden(params, x, cfg, positions, remat=remat)


def hybrid_init_state(cfg: ModelConfig, B: int, S_cache: int, dtype):
    period = cfg.hybrid.period
    n_super = cfg.num_layers // period
    d_in, H, hd = S._mamba_dims(cfg)
    n = cfg.ssm_state
    Kc = cfg.ssm_conv - 1
    mamba_state = (
        jnp.zeros((n_super, period, B, Kc, d_in + 2 * n), dtype),
        jnp.zeros((n_super, period, B, H, hd, n), dtype))
    # shared attention: params shared, but each application has its own KV
    kv = (jnp.zeros((n_super, B, S_cache, cfg.num_kv_heads, cfg.head_dim),
                    dtype),
          jnp.zeros((n_super, B, S_cache, cfg.num_kv_heads, cfg.head_dim),
                    dtype))
    return (mamba_state, kv)


def hybrid_prefill(params, tokens, cfg: ModelConfig, S_max: int):
    B, Stok = tokens.shape
    period = cfg.hybrid.period
    n_super = cfg.num_layers // period
    scfg = _hybrid_shared_cfg(cfg)
    x = L.embed(params["embed"], tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(Stok), (B, Stok))
    mamba = jax.tree.map(
        lambda a: a.reshape(n_super, period, *a.shape[1:]), params["mamba"])

    def super_body(h, bp):
        def inner(h2, ip):
            y, st = S.mamba2_fwd(ip["mamba"],
                                 L.apply_norm(ip["ln"], h2), cfg)
            return h2 + y, st
        h, mstates = jax.lax.scan(inner, h, bp)
        h, kv, _ = block_fwd(params["shared"], h, scfg, positions,
                             mode="prefill")
        return h, (mstates, kv)

    x, (mstates, kvs) = jax.lax.scan(super_body, x, mamba)
    x = L.apply_norm(params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x[:, -1:, :], cfg)
    k0, v0 = (jnp.zeros((n_super, B, S_max, cfg.num_kv_heads,
                         cfg.head_dim), x.dtype),) * 2
    cache = ((mstates[0], mstates[1]),
             (jax.lax.dynamic_update_slice(k0, kvs[0], (0, 0, 0, 0, 0)),
              jax.lax.dynamic_update_slice(v0, kvs[1], (0, 0, 0, 0, 0))))
    return logits, cache


def hybrid_decode_step(params, token, state, pos, cfg: ModelConfig):
    period = cfg.hybrid.period
    n_super = cfg.num_layers // period
    scfg = _hybrid_shared_cfg(cfg)
    mamba_state, kv = state
    x = L.embed(params["embed"], token, cfg)
    mamba = jax.tree.map(
        lambda a: a.reshape(n_super, period, *a.shape[1:]), params["mamba"])

    def super_body(h, xs):
        bp, mst, kvc = xs

        def inner(h2, ys):
            ip, st1 = ys
            y, new_st = S.mamba2_fwd(ip["mamba"],
                                     L.apply_norm(ip["ln"], h2), cfg,
                                     state=st1)
            return h2 + y, new_st

        h, new_mst = jax.lax.scan(inner, h, (bp, mst))
        h, new_kv, _ = block_fwd(params["shared"], h, scfg, None,
                                 mode="decode", cache=kvc, pos=pos)
        return h, (new_mst, new_kv)

    x, (new_mamba, new_kv) = jax.lax.scan(
        super_body, x, (mamba, mamba_state, kv))
    x = L.apply_norm(params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg), (new_mamba, new_kv)


# ======================================================================
# Family: audio (whisper) — encoder-decoder with stub frontend
# ======================================================================
def init_encdec(cfg: ModelConfig, key):
    ks = jax.random.split(key, 5)
    p, sp = {}, {}
    p["embed"], sp["embed"] = L.init_embedding(cfg, ks[0])

    def enc_block(k):
        bp, bs = {}, {}
        bp["ln1"], bs["ln1"] = L.init_norm(cfg, cfg.d_model)
        bp["attn"], bs["attn"] = L.init_attention(cfg, k)
        bp["ln2"], bs["ln2"] = L.init_norm(cfg, cfg.d_model)
        bp["mlp"], bs["mlp"] = L.init_mlp(cfg.d_model, cfg.d_ff,
                                          jax.random.fold_in(k, 1))
        return bp, bs

    p["enc"], sp["enc"] = _vmap_init(enc_block, cfg.enc_layers, ks[1])
    p["dec"], sp["dec"] = _vmap_init(
        lambda k: init_block(cfg, k, cross=True), cfg.num_layers, ks[2])
    p["ln_enc"], sp["ln_enc"] = L.init_norm(cfg, cfg.d_model)
    p["ln_f"], sp["ln_f"] = L.init_norm(cfg, cfg.d_model)
    return _f32_to(p, jnp.dtype(cfg.dtype)), sp


def encode(params, frames, cfg: ModelConfig):
    """frames: precomputed frame embeddings (B, S_enc, d) — stub frontend."""
    B, Se, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(Se), (B, Se))

    def body(h, bp):
        a = L.attention_fwd(bp["attn"], L.apply_norm(bp["ln1"], h), cfg,
                            positions, causal=False)
        h = h + a
        h = h + L.mlp_fwd(bp["mlp"], L.apply_norm(bp["ln2"], h))
        return h, None

    x, _ = jax.lax.scan(body, frames.astype(jnp.dtype(cfg.dtype)),
                        params["enc"])
    return L.apply_norm(params["ln_enc"], x)


def encdec_forward_train(params, batch, cfg: ModelConfig, *, remat=True):
    frames, dec_tokens = batch
    enc_out = encode(params, frames, cfg)
    B, Sd = dec_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sd), (B, Sd))
    x = L.embed(params["embed"], dec_tokens, cfg)

    def body(carry, bp):
        h = carry
        kv = L.encode_kv(bp["xattn"], enc_out, cfg)
        h, _, _ = block_fwd(bp, h, cfg, positions, mode="train", enc_kv=kv)
        return h, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["dec"])
    return L.apply_norm(params["ln_f"], x), jnp.zeros((), jnp.float32)


def encdec_prefill(params, batch, cfg: ModelConfig, S_max: int):
    """Encode audio + prefill decoder prompt.  Cache = (self_kv, cross_kv)."""
    frames, dec_tokens = batch
    enc_out = encode(params, frames, cfg)
    B, Sd = dec_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sd), (B, Sd))
    x = L.embed(params["embed"], dec_tokens, cfg)

    def body(h, bp):
        xkv = L.encode_kv(bp["xattn"], enc_out, cfg)
        h, kv, _ = block_fwd(bp, h, cfg, positions, mode="prefill",
                             enc_kv=xkv)
        return h, (kv, xkv)

    x, (kvs, xkvs) = jax.lax.scan(body, x, params["dec"])
    x = L.apply_norm(params["ln_f"], x)
    logits = L.lm_logits(params["embed"], x[:, -1:, :], cfg)
    S_dec = min(S_max, cfg.dec_max_len)
    k0 = jnp.zeros((cfg.num_layers, B, S_dec, cfg.num_kv_heads,
                    cfg.head_dim), x.dtype)
    cache = ((jax.lax.dynamic_update_slice(k0, kvs[0], (0, 0, 0, 0, 0)),
              jax.lax.dynamic_update_slice(k0, kvs[1], (0, 0, 0, 0, 0))),
             xkvs)
    return logits, cache


def encdec_decode_step(params, token, cache, pos, cfg: ModelConfig):
    self_kv, cross_kv = cache
    x = L.embed(params["embed"], token, cfg)

    def body(h, xs):
        bp, c_self, c_cross = xs
        hn = L.apply_norm(bp["ln1"], h)
        a, new_self = L.attention_decode(bp["attn"], hn, c_self, cfg, pos)
        h = h + a
        hx = L.apply_norm(bp["ln_x"], h)
        h = h + L.cross_attention_fwd(bp["xattn"], hx, c_cross, cfg)
        h = h + L.mlp_fwd(bp["mlp"], L.apply_norm(bp["ln2"], h))
        return h, new_self

    x, new_self = jax.lax.scan(body, x,
                               (params["dec"], self_kv, cross_kv))
    x = L.apply_norm(params["ln_f"], x)
    return L.lm_logits(params["embed"], x, cfg), (new_self, cross_kv)


# ======================================================================
# Loss: chunked-vocab cross entropy (never materializes (B,S,V) at once)
# ======================================================================
def lm_loss_from_hidden(params, hidden, targets, cfg: ModelConfig,
                        chunk: int = 512):
    """hidden: (B,S,d); targets: (B,S) int32.  Scans over sequence chunks
    so the fp32 logits live only one chunk at a time."""
    B, Sq, d = hidden.shape
    chunk = min(chunk, Sq)
    while Sq % chunk:       # largest divisor of Sq not above the target
        chunk -= 1
    n = Sq // chunk
    h = hidden.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    y = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        hc, yc = xs
        logits = L.lm_logits(params["embed"], hc, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return carry + (lse - ll).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, y))
    return total / (B * Sq)


# ======================================================================
# Family dispatch
# ======================================================================
@dataclasses.dataclass(frozen=True)
class ModelApi:
    init: Any
    forward_train: Any      # (params, inputs, cfg) -> (hidden, aux)
    prefill: Any
    decode_step: Any


def get_api(cfg: ModelConfig) -> ModelApi:
    if cfg.enc_dec:
        return ModelApi(init_encdec, encdec_forward_train, encdec_prefill,
                        encdec_decode_step)
    if cfg.family == "ssm":
        return ModelApi(init_xlstm, xlstm_forward_train, xlstm_prefill,
                        xlstm_decode_step)
    if cfg.family == "hybrid":
        return ModelApi(init_hybrid, hybrid_forward_train, hybrid_prefill,
                        hybrid_decode_step)
    return ModelApi(init_lm, lm_forward_train, lm_prefill, lm_decode_step)
