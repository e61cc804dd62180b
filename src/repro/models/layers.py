"""Shared layer library: norms, rotary, GQA/MLA attention (prefill +
cached decode), gated MLP, capacity-based MoE with static shapes.

Pure-functional JAX: params are nested dicts of arrays; every init_*
returns (params, partition-spec-tree) so launch/sharding can pjit without
a framework dependency.  Attention over long sequences is q-chunked
(scan) so the 32k prefill compiles with bounded live memory.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from .config import MLAConfig, ModelConfig, MoEConfig

# Axis names used by every PartitionSpec: "data" (+"pod" outside), "model".
MODEL = "model"
DATA = "data"


def _init(key, shape, scale_axis=0):
    scale = 1.0 / math.sqrt(max(1, shape[scale_axis]))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}, \
            {"scale": P(None), "bias": P(None)}
    return {"scale": jnp.ones((d,))}, {"scale": P(None)}


def apply_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        var = (xf ** 2).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """qk-norm: per-head RMS norm (qwen3)."""
    xf = x.astype(jnp.float32)
    var = (xf ** 2).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary embeddings
# ----------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float, pct: float = 1.0):
    rot = int(head_dim * pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, pct: float = 1.0):
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    inv, rot = rope_frequencies(d, theta, pct)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]
    cos = cos[..., :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(*xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# Attention core: chunked causal softmax attention
# ----------------------------------------------------------------------
def _mask_bias(q_pos, k_pos, window: int, causal: bool):
    ok = (k_pos[None, :] <= q_pos[:, None]) if causal else \
        jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if window:
        ok &= (k_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(ok, 0.0, -1e30)


def sdpa(q, k, v, q_pos, k_pos, *, causal=True, window=0, chunk=1024):
    """q: (B,Sq,H,D) k/v: (B,Sk,KV,Dk/Dv).  GQA by head repetition.
    Scans over query chunks so Sq x Sk scores never fully materialize.
    On TPU, self-attention dispatches to the Pallas flash kernel
    (kernels/flash_attention); the chunked path is the portable
    fallback and the kernel's numerical reference."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    scale = 1.0 / math.sqrt(D)

    if (jax.default_backend() == "tpu" and causal and window == 0
            and Sq == k.shape[1] and Sq % 128 == 0):
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=True).astype(q.dtype)

    def attend(qc, qp):
        # qc: (B,C,H,D)
        kk = jnp.repeat(k, rep, axis=2) if rep > 1 else k
        vv = jnp.repeat(v, rep, axis=2) if rep > 1 else v
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kk,
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask_bias(qp, k_pos, window, causal)[None, None]
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    if Sq <= chunk:
        return attend(q, q_pos)
    n = Sq // chunk

    def body(_, qs):
        qc, qp = qs
        return None, attend(qc, qp)

    qr = q.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)
    pr = q_pos.reshape(n, chunk)
    _, out = jax.lax.scan(body, None, (qr, pr))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)


# ----------------------------------------------------------------------
# GQA attention block
# ----------------------------------------------------------------------
def init_attention(cfg: ModelConfig, key) -> tuple[dict, dict]:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d, qd)),
        "wk": _init(ks[1], (d, kvd)),
        "wv": _init(ks[2], (d, kvd)),
        "wo": _init(ks[3], (qd, d)),
    }
    spec = {
        "wq": P(None, MODEL), "wk": P(None, MODEL),
        "wv": P(None, MODEL), "wo": P(MODEL, None),
    }
    if cfg.qkv_bias:
        p |= {"bq": jnp.zeros((qd,)), "bk": jnp.zeros((kvd,)),
              "bv": jnp.zeros((kvd,))}
        spec |= {"bq": P(MODEL), "bk": P(MODEL), "bv": P(MODEL)}
    if cfg.qk_norm:
        p |= {"q_norm": jnp.ones((cfg.head_dim,)),
              "k_norm": jnp.ones((cfg.head_dim,))}
        spec |= {"q_norm": P(None), "k_norm": P(None)}
    return p, spec


def attention_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q, k, v = (q + p["bq"].astype(x.dtype),
                   k + p["bk"].astype(x.dtype),
                   v + p["bv"].astype(x.dtype))
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.rotary_pct > 0:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    return q, k, v


def attention_fwd(p, x, cfg: ModelConfig, positions, *, causal=True,
                  project=True):
    """Full-sequence attention (training / encoder).  project=False
    returns the concatenated head outputs (for fused projections)."""
    B, S, _ = x.shape
    q, k, v = attention_qkv(p, x, cfg, positions)
    o = sdpa(q, k, v, positions[0], positions[0], causal=causal,
             window=cfg.attn_window)
    o = o.reshape(B, S, cfg.q_dim)
    return o @ p["wo"].astype(x.dtype) if project else o


def attention_prefill(p, x, cfg: ModelConfig, positions, *,
                      project=True):
    """Returns (out, (k_cache, v_cache))."""
    B, S, _ = x.shape
    q, k, v = attention_qkv(p, x, cfg, positions)
    o = sdpa(q, k, v, positions[0], positions[0], causal=True,
             window=cfg.attn_window)
    o = o.reshape(B, S, cfg.q_dim)
    return (o @ p["wo"].astype(x.dtype) if project else o), (k, v)


def attention_decode(p, x, cache, cfg: ModelConfig, pos, *,
                     project=True):
    """x: (B,1,d); cache k/v: (B,S,KV,D); pos: scalar OR (B,) vector of
    per-slot positions (continuous batching: slots advance independently).
    Writes the new k/v at each slot's position and attends over <= pos."""
    B = x.shape[0]
    k_cache, v_cache = cache
    S = k_cache.shape[1]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = attention_qkv(p, x, cfg, pos_vec[:, None])
    b_idx = jnp.arange(B)
    k_cache = k_cache.at[b_idx, pos_vec].set(k[:, 0])
    v_cache = v_cache.at[b_idx, pos_vec].set(v[:, 0])
    k_pos = jnp.arange(S)
    valid = (k_pos[None, :] <= pos_vec[:, None])          # (B, S)
    if cfg.attn_window:
        valid &= (k_pos[None, :] > pos_vec[:, None] - cfg.attn_window)
    rep = cfg.num_heads // cfg.num_kv_heads
    kk = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    vv = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(cfg.head_dim) + jnp.where(valid, 0.0, -1e30)[
        :, None, None, :]
    prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob, vv)
    o = o.reshape(B, 1, cfg.q_dim)
    out = o @ p["wo"].astype(x.dtype) if project else o
    return out, (k_cache, v_cache)


# ----------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV — cache stores (c_kv, k_rope)
# DSA (DeepSeek-V3.2-Exp): a lightning indexer picks each query's
# top-k cached tokens; the cache also keeps the indexer's keys
# ----------------------------------------------------------------------
def init_mla(cfg: ModelConfig, key):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    ks = [*ks, *jax.random.split(jax.random.fold_in(key, 1), 4)]
    p = {
        "w_dkv": _init(ks[0], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "w_uk": _init(ks[1], (m.kv_lora_rank, H, m.qk_nope_head_dim)),
        "w_uv": _init(ks[2], (m.kv_lora_rank, H, m.v_head_dim)),
        "wo": _init(ks[4], (H * m.v_head_dim, d), scale_axis=0),
        "kv_norm": jnp.ones((m.kv_lora_rank,)),
    }
    spec = {
        "w_dkv": P(None, None), "w_uk": P(None, MODEL, None),
        "w_uv": P(None, MODEL, None),
        "wo": P(MODEL, None), "kv_norm": P(None),
    }
    if m.q_lora_rank:
        p["w_dq"] = _init(ks[3], (d, m.q_lora_rank))
        p["q_norm"] = jnp.ones((m.q_lora_rank,))
        p["w_uq"] = _init(ks[5], (m.q_lora_rank, H, qk))
        spec.update(w_dq=P(None, None), q_norm=P(None),
                    w_uq=P(None, MODEL, None))
    else:
        p["w_q"] = _init(ks[3], (d, H, qk))
        spec["w_q"] = P(None, MODEL, None)
    if cfg.dsa:
        x = cfg.dsa
        p["idx_wq"] = _init(ks[6], (m.q_lora_rank or d, x.index_n_heads,
                                    x.index_head_dim))
        p["idx_wk"] = _init(ks[7], (d, x.index_head_dim))
        p["idx_k_norm"] = {"scale": jnp.ones((x.index_head_dim,)),
                           "bias": jnp.zeros((x.index_head_dim,))}
        p["idx_ww"] = _init(ks[8], (d, x.index_n_heads))
        spec.update(idx_wq=P(None, None, None), idx_wk=P(None, None),
                    idx_k_norm={"scale": P(None), "bias": P(None)},
                    idx_ww=P(None, None))
    return p, spec


def _mla_q(p, x, cfg, positions):
    """(q_nope, q_rope, q_src): the query's two parts per head, and what
    the indexer's queries read (q-LoRA's normed latent, else x)."""
    m = cfg.mla
    if m.q_lora_rank:
        src = apply_norm({"scale": p["q_norm"]},
                         x @ p["w_dq"].astype(x.dtype))
        q = jnp.einsum("bsr,rhe->bshe", src, p["w_uq"].astype(x.dtype))
    else:
        src = x
        q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"].astype(x.dtype))
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope, src


def _idx_rope(x, positions, cfg):
    """Rotary embedding on the indexer head's first ``qk_rope_head_dim``
    features."""
    d = x.shape[-1]
    return apply_rope(x, positions, cfg.rope_theta,
                      pct=cfg.mla.qk_rope_head_dim / d)


def dsa_index_keys(p, x, cfg, positions):
    """The indexer's key of each token: (B, S, index_head_dim)."""
    k = apply_norm(p["idx_k_norm"], x @ p["idx_wk"].astype(x.dtype))
    return _idx_rope(k[..., None, :], positions, cfg)[..., 0, :]


def dsa_index_scores(p, x, q_src, k_idx, cfg, positions):
    """The lightning indexer's score of every cached token for every
    query, I[b, s, t] = sum_j w[b, s, j] relu(q[b, s, j] . k[b, t]),
    (B, Sq, Sk); the weights w carry 1/sqrt(heads * head_dim)."""
    x_ = cfg.dsa
    q = jnp.einsum("bsr,rjc->bsjc", q_src, p["idx_wq"].astype(x.dtype))
    q = _idx_rope(q, positions, cfg)
    w = (x @ p["idx_ww"].astype(x.dtype)) * (
        (x_.index_n_heads * x_.index_head_dim) ** -0.5)
    s = jnp.einsum("bsjc,btc->bsjt", q, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bsj,bsjt->bst", w.astype(jnp.float32),
                      jax.nn.relu(s))


def dsa_select(scores, visible, topk: int):
    """Each query's ``topk`` best-scored tokens among those it may see
    (all of them when it sees fewer): a (B, Sq, Sk) mask."""
    s = jnp.where(visible, scores, -jnp.inf)
    kth = jax.lax.top_k(s, min(topk, s.shape[-1]))[0][..., -1:]
    return visible & (s >= kth)


def _mla_ckv(p, x, cfg, positions):
    m = cfg.mla
    ckv = x @ p["w_dkv"].astype(x.dtype)
    c_kv = apply_norm({"scale": p["kv_norm"]}, ckv[..., :m.kv_lora_rank])
    k_rope = apply_rope(ckv[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_fwd(p, x, cfg: ModelConfig, positions, cache=None, pos=None):
    """Absorbed-matmul MLA.  Training/prefill when cache is None or a
    fresh cache is produced; decode when (cache, pos) given.  With DSA
    (``cfg.dsa``) each query attends to its indexer-selected tokens only
    and the cache holds the indexer's keys third."""
    m = cfg.mla
    B = x.shape[0]
    pos_vec = (None if pos is None else
               jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)))
    q_positions = positions if pos is None else pos_vec[:, None]
    q_nope, q_rope, q_src = _mla_q(p, x, cfg, q_positions)
    # absorb W_uk into q: score space is the compressed rank r
    q_c = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"].astype(x.dtype))
    if pos is None:
        c_kv, k_rope = _mla_ckv(p, x, cfg, positions)
        k_pos = positions[0]
        q_pos = positions[0]
        causal = (k_pos[None, :] <= q_pos[:, None])[None]   # (1, Sq, Sk)
        new_cache = (c_kv, k_rope)
        if cfg.dsa:
            k_idx = dsa_index_keys(p, x, cfg, positions)
            new_cache += (k_idx,)
    else:
        c_new, kr_new = _mla_ckv(p, x, cfg, pos_vec[:, None])
        b_idx = jnp.arange(B)
        c_kv = cache[0].at[b_idx, pos_vec].set(c_new[:, 0])
        k_rope = cache[1].at[b_idx, pos_vec].set(kr_new[:, 0])
        k_pos = jnp.arange(c_kv.shape[1])
        causal = (k_pos[None, None, :]
                  <= pos_vec[:, None, None])                # (B, 1, Sk)
        new_cache = (c_kv, k_rope)
        if cfg.dsa:
            ki_new = dsa_index_keys(p, x, cfg, pos_vec[:, None])
            k_idx = cache[2].at[b_idx, pos_vec].set(ki_new[:, 0])
            new_cache += (k_idx,)
    if cfg.dsa:
        causal = dsa_select(
            dsa_index_scores(p, x, q_src, k_idx, cfg, q_positions),
            causal, cfg.dsa.index_topk)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bshr,bkr->bhsk", q_c, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshe,bke->bhsk", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    s = s + jnp.where(causal, 0.0, -1e30)[:, None]
    prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhsk,bkr->bshr", prob, c_kv)
    o = jnp.einsum("bshr,rhe->bshe", ctx, p["w_uv"].astype(x.dtype))
    out = o.reshape(B, x.shape[1], -1) @ p["wo"].astype(x.dtype)
    return out, new_cache


# ----------------------------------------------------------------------
# Cross attention (whisper decoder)
# ----------------------------------------------------------------------
def cross_attention_fwd(p, x, enc_kv, cfg: ModelConfig):
    B, S, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(
        B, S, cfg.num_heads, cfg.head_dim)
    k, v = enc_kv
    o = sdpa(q, k, v, jnp.arange(S), jnp.arange(k.shape[1]), causal=False)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"].astype(x.dtype)


def encode_kv(p, enc_out, cfg: ModelConfig):
    B, S, _ = enc_out.shape
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ----------------------------------------------------------------------
# Gated MLP
# ----------------------------------------------------------------------
def init_mlp(d: int, d_ff: int, key):
    ks = jax.random.split(key, 3)
    p = {"wi": _init(ks[0], (d, d_ff)), "wg": _init(ks[1], (d, d_ff)),
         "wo": _init(ks[2], (d_ff, d))}
    spec = {"wi": P(None, MODEL), "wg": P(None, MODEL), "wo": P(MODEL, None)}
    return p, spec


def mlp_fwd(p, x):
    h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    return h @ p["wo"].astype(x.dtype)


def mlp_hidden(p, x):
    """Gated hidden activations without the output projection."""
    return jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (
        x @ p["wi"].astype(x.dtype))


# ----------------------------------------------------------------------
# MoE: top-k routing with static-capacity gather/scatter dispatch.
# Expert dimension shards over "model" (expert parallelism).
# ----------------------------------------------------------------------
def init_moe(cfg: ModelConfig, key):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, m.num_experts)),
        "wi": _init(ks[1], (m.num_experts, d, m.expert_d_ff), 1),
        "wg": _init(ks[2], (m.num_experts, d, m.expert_d_ff), 1),
        "wo": _init(ks[3], (m.num_experts, m.expert_d_ff, d), 1),
    }
    spec = {
        "router": P(None, None),
        "wi": P(MODEL, None, None), "wg": P(MODEL, None, None),
        "wo": P(MODEL, None, None),
    }
    if m.num_shared_experts:
        sp, ss = init_mlp(d, m.shared_d_ff * m.num_shared_experts, ks[4])
        p["shared"] = sp
        spec["shared"] = ss
    return p, spec


def moe_fwd(p, x, cfg: ModelConfig):
    """x: (B,S,d).  Static-shape dispatch: argsort tokens by expert,
    contiguous per-expert segments padded/truncated to capacity C."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)           # (T, k)
    top_w = (top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)
             ).astype(x.dtype)

    TK = T * m.top_k
    C = max(1, int(math.ceil(TK / m.num_experts * m.capacity_factor)))
    flat_e = top_e.reshape(TK)
    order = jnp.argsort(flat_e)                             # stable
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(m.num_experts),
                                 side="left")
    seg_end = jnp.searchsorted(sorted_e, jnp.arange(m.num_experts),
                               side="right")
    slot = seg_start[:, None] + jnp.arange(C)[None, :]      # (E, C)
    valid = slot < seg_end[:, None]
    slot = jnp.clip(slot, 0, TK - 1)
    src = order[slot]                                       # (E, C) flat idx
    tok = src // m.top_k
    x_e = xt[tok] * valid[..., None].astype(x.dtype)        # (E, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e,
                               p["wg"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", x_e, p["wi"].astype(x.dtype))
    y_e = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))

    w = top_w.reshape(TK)[src] * valid.astype(x.dtype)      # (E, C)
    out = jnp.zeros((T, d), x.dtype).at[tok.reshape(-1)].add(
        (y_e * w[..., None]).reshape(-1, d))
    if "shared" in p:
        out = out + mlp_fwd(p["shared"], xt)
    # auxiliary load-balancing loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((m.num_experts,)).at[flat_e].add(1.0) / TK
    aux = m.num_experts * jnp.sum(me * ce)
    return out.reshape(B, S, d), aux


# ----------------------------------------------------------------------
# Embeddings / LM head
# ----------------------------------------------------------------------
def init_embedding(cfg: ModelConfig, key):
    ks = jax.random.split(key, 2)
    p = {"tok": _init(ks[0], (cfg.vocab_size, cfg.d_model), 1) * 0.02 * (
        cfg.d_model ** 0.5)}
    spec = {"tok": P(MODEL, None)}
    if not cfg.tie_embeddings:
        p["head"] = _init(ks[1], (cfg.d_model, cfg.vocab_size))
        spec["head"] = P(None, MODEL)
    return p, spec


def embed(p, tokens, cfg: ModelConfig):
    table = p["tok"].astype(jnp.dtype(cfg.dtype))
    mesh = jax.typeof(table).sharding.mesh
    if AxisType.Explicit not in mesh.axis_types:
        return table[tokens]
    # the table is sharded on rows; under Explicit axes the gather states
    # its output sharding: rows follow the tokens, features replicated
    spec = jax.typeof(tokens).sharding.spec
    return table.at[tokens].get(
        out_sharding=NamedSharding(mesh, P(*spec, None)))


def lm_logits(p, x, cfg: ModelConfig):
    w = p.get("head", p["tok"].T).astype(x.dtype)
    return x @ w
