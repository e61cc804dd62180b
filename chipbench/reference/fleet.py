"""The fleet's matmuls, per device, from the widths a configuration file
states: the plain reference of what a fleet sweep has to extract.

Each model is a dict of widths (``d_model``, ``layers``, ``heads``, ...)
and of the blocks it stacks.  One forward pass of a phase runs:

* per block, by kind: ``attn`` (fused QKV and output projections, then
  the score and value products per head and sequence), ``mamba2``
  (in, out and B/C/dt projections), ``mlstm`` / ``slstm`` (up and down
  projections); and, where the model says ``ffn``, a gated FFN (fused
  gate and up, then down) or its mixture of experts (a router, the
  routed experts at ``tokens x top_k / experts`` rows each, the shared
  experts at every token);
* a shared attention block (``shared_attn``) applied once per
  ``period`` blocks, an encoder (``encoder``) at prefill and cross
  attention in every decoder block, and the LM head.

Prefill runs ``batch`` sequences of ``seq_len`` tokens (an encoder-decoder
caps the decoder at ``dec_max_len``); decode runs one token for each of
``batch`` sequences against a cache of ``seq_len``.  ``attn_window`` caps
the keys an attention sees.  Entries of one name and shape run as one,
their counts summed.  Per device, under a ``data`` x ``model`` mesh: token
rows split over ``data``; column-parallel weights split their outputs and
row-parallel weights their inputs over ``model``; score products split
their head-and-sequence count over ``model``, then ``data``.  A size the
axis does not divide stays whole.
"""
from __future__ import annotations

#: (name, M, K, N, count, tp): tp is "col", "row", "none" or "attn"
Entry = tuple


def _attn(m: dict, T: int) -> list:
    d = m["d_model"]
    if "mla" in m:
        a, h = m["mla"], m["heads"]
        return [
            ("mla_q_proj", T, d,
             h * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]), 1, "col"),
            ("mla_kv_a_proj", T, d,
             a["kv_lora_rank"] + a["qk_rope_head_dim"], 1, "none"),
            ("mla_kv_b_proj", T, a["kv_lora_rank"],
             h * (a["qk_nope_head_dim"] + a["v_head_dim"]), 1, "col"),
            ("mla_o_proj", T, h * a["v_head_dim"], d, 1, "row"),
        ]
    q = m["heads"] * m["head_dim"]
    kv = m["kv_heads"] * m["head_dim"]
    return [("attn_qkv", T, d, q + 2 * kv, 1, "col"),
            ("attn_o_proj", T, q, d, 1, "row")]


def _scores(m: dict, prefix: str, q_len: int, kv_len: int, seqs: int,
            times: int = 1) -> list:
    if "mla" in m:
        a = m["mla"]
        qk, v = a["qk_nope_head_dim"] + a["qk_rope_head_dim"], a["v_head_dim"]
    else:
        qk = v = m["head_dim"]
    n = m["heads"] * seqs * times
    return [(f"{prefix}_qk", q_len, qk, kv_len, n, "attn"),
            (f"{prefix}_av", q_len, kv_len, v, n, "attn")]


def _ffn(m: dict, T: int) -> list:
    d = m["d_model"]
    if "moe" in m:
        e = m["moe"]
        rows = max(1, T * e["top_k"] // e["experts"])
        out = [("moe_router", T, d, e["experts"], 1, "none"),
               ("moe_expert_gate_up", rows, d, 2 * e["expert_d_ff"],
                e["experts"], "col"),
               ("moe_expert_down", rows, e["expert_d_ff"], d,
                e["experts"], "row")]
        if e.get("shared_experts"):
            s = e["shared_experts"]
            out += [("moe_shared_gate_up", T, d, 2 * e["shared_d_ff"], s,
                     "col"),
                    ("moe_shared_down", T, e["shared_d_ff"], d, s, "row")]
        return out
    return [("ffn_gate_up", T, d, 2 * m["d_ff"], 1, "col"),
            ("ffn_down", T, m["d_ff"], d, 1, "row")]


def network(m: dict, phase: str, seq_len: int, batch: int) -> list:
    """Global (unsharded) entries of one forward pass, merged."""
    d = m["d_model"]
    dec = min(seq_len, m.get("dec_max_len", seq_len))
    if phase == "prefill":
        q_len, kv_len, T = dec, dec, dec * batch
    else:
        q_len, kv_len, T = 1, dec, batch
    if m.get("attn_window"):
        kv_len = min(kv_len, m["attn_window"])
    out = []
    kinds = m["blocks"]
    for i in range(m["layers"]):
        kind = kinds[i % len(kinds)]
        if kind == "attn":
            out += _attn(m, T) + _scores(m, "attn", q_len, kv_len, batch)
        elif kind == "mamba2":
            di = m["expand"] * d
            out += [("ssm_in_proj", T, d, 2 * di, 1, "col"),
                    ("ssm_out_proj", T, di, d, 1, "row"),
                    ("ssm_bcdt_proj", T, di, 2 * m["ssm_state"] + 3, 1,
                     "none")]
        else:
            di = m["expand"] * d
            out += [(f"{kind}_up_proj", T, d, 2 * di, 1, "col"),
                    (f"{kind}_down_proj", T, di, d, 1, "row")]
        if m["ffn"]:
            out += _ffn(m, T)
    if "shared_attn" in m:
        times = m["layers"] // m["shared_attn"]["period"]
        sd = m["shared_attn"]["d_ff"]
        q = m["heads"] * m["head_dim"]
        kv = m["kv_heads"] * m["head_dim"]
        out += [("shared_attn_qkv", T, d, q + 2 * kv, times, "col"),
                ("shared_attn_o_proj", T, q, d, times, "row"),
                ("shared_ffn_gate_up", T, d, 2 * sd, times, "col"),
                ("shared_ffn_down", T, sd, d, times, "row")]
        out += _scores(m, "shared_attn", q_len, kv_len, batch, times)
    if "encoder" in m:
        frames, n = m["encoder"]["frames"], m["encoder"]["layers"]
        Te, L = frames * batch, m["layers"]
        if phase == "prefill":
            out += [("enc_qkv", Te, d, 3 * d, n, "col"),
                    ("enc_o_proj", Te, d, d, n, "row"),
                    ("enc_ffn_gate_up", Te, d, 2 * m["d_ff"], n, "col"),
                    ("enc_ffn_down", Te, m["d_ff"], d, n, "row")]
            out += _scores(m, "enc_attn", frames, frames, batch, n)
            out += [("cross_k_proj", Te, d, d, L, "col"),
                    ("cross_v_proj", Te, d, d, L, "col")]
        out += [("cross_q_proj", T, d, d, L, "col"),
                ("cross_o_proj", T, d, d, L, "row")]
        out += _scores(m, "cross_attn", q_len, frames, batch, L)
    out.append(("lm_head", T, d, m["vocab"], 1, "col"))
    merged: dict = {}
    for name, M, K, N, count, tp in out:
        key = (name, M, K, N, tp)
        merged[key] = merged.get(key, 0) + count
    return [(name, M, K, N, count, tp)
            for (name, M, K, N, tp), count in merged.items()]


def _split(size: int, parts: int) -> int:
    return size // parts if size % parts == 0 else size


def per_device(entries: list, mesh: dict) -> list:
    """Per-device entries under a ``{"data": n, "model": n}`` mesh."""
    data, model = mesh["data"], mesh["model"]
    out = []
    for name, M, K, N, count, tp in entries:
        if tp == "attn":
            count = max(1, _split(_split(count, model), data))
        else:
            M = max(1, _split(M, data))
            if tp == "col":
                N = _split(N, model)
            elif tp == "row":
                K = _split(K, model)
        out.append((name, M, K, N, count, tp))
    return out


def fleet(cfg: dict, models=None) -> dict:
    """``(model, phase) -> per-device entries`` of a fleet configuration
    (``models``: a subset of its model names, default all)."""
    out = {}
    for name in models or cfg["models"]:
        m = cfg["models"][name]
        for phase in cfg["phases"]:
            out[name, phase] = per_device(
                network(m, phase, cfg["seq_len"], cfg["batch"][phase]),
                cfg["mesh"])
    return out
