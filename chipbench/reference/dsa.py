"""DeepSeek-V3.2-Exp's long-context decode as the reference models it,
from the widths a configuration file states (the keys of the model's
``config.json``): the plain reference of what a DSA fleet sweep has to
extract and evaluate.  It imports nothing of the program.

One decode step (``batch`` sequences, one token each, a cache of
``seq_len`` tokens) runs, in each of the ``num_hidden_layers`` layers:

* MLA with a low-rank query (``q_lora_rank``) in its absorbed form: the
  query's down and up projections, the latent's projection (``kv_lora_rank``
  + ``qk_rope_head_dim`` wide), per head W_UK (folding the query into the
  latent) and W_UV (out of it), the output projection;
* the lightning indexer: its query (``index_n_heads`` x
  ``index_head_dim`` from the query latent), key and head weights (from
  the hidden state), and its scores over the whole cache, per head;
* the score and value products of every head against the one shared
  latent cache, on the ``index_topk`` tokens the indexer selects: the
  latent cache, operand B of both, is a selection of k of its L fibres
  (its columns in the score product, its rows in the value product);
  the probabilities stay dense over the cache;
* the FFN: ``first_k_dense_replace`` dense layers (``intermediate_size``),
  then the routed experts (``n_routed_experts``, ``num_experts_per_tok``,
  ``moe_intermediate_size``) with a router and ``n_shared_experts``;

then the LM head.  Per device under a ``data`` x ``model`` mesh: token
rows over ``data``; column-parallel weights split N, row-parallel K,
per-head weights their head count over ``model``; the attention products,
their heads in M, split M over ``model`` and their sequences over
``data``.  A size the axis does not divide stays whole.

A selection of k of L fibres, each as long as the operand's other rank,
is empty on a tile of c = ceil(t / fibre) whole fibres with probability
C(L - c, k) / C(L, k), evaluated here as the product over the tile's
fibres of (1 - k / (L - j)); its density is k / L.
"""
from __future__ import annotations

import dataclasses
import math as _math

from . import num as math
from .dataflow import analyze_dataflow
from .density import DensityModel, make_density_model
from .microarch import evaluate_microarch
from .presets import Design, design as preset_design, tpu_v5e_arch
from .sparse import analyze_sparse
from .taxonomy import ActionSAF, RankFormat, SAFKind, SAFSpec, TensorFormat
from .workload import matmul

#: (name, M, K, N, count, tp, select): tp is "col", "row", "none",
#: "per_head" or "heads"; select is (tensor, rank, k) or None
Entry = tuple


def _decode_layer(w: dict, T: int, ctx: int, seqs: int) -> list:
    d, h = w["hidden_size"], w["num_attention_heads"]
    ql, r = w["q_lora_rank"], w["kv_lora_rank"]
    nope, rope, v = (w["qk_nope_head_dim"], w["qk_rope_head_dim"],
                     w["v_head_dim"])
    ih, idim, k = w["index_n_heads"], w["index_head_dim"], w["index_topk"]
    return [
        ("mla_q_a", T, d, ql, 1, "none", None),
        ("mla_q_b", T, ql, h * (nope + rope), 1, "col", None),
        ("mla_kv_a", T, d, r + rope, 1, "none", None),
        ("mla_uk", T, nope, r, h, "per_head", None),
        ("mla_uv", T, r, v, h, "per_head", None),
        ("mla_o", T, h * v, d, 1, "row", None),
        ("idx_q", T, ql, ih * idim, 1, "col", None),
        ("idx_k", T, d, idim, 1, "none", None),
        ("idx_w", T, d, ih, 1, "none", None),
        ("idx_scores", ih, idim, ctx, seqs, "heads", None),
        ("dsa_qk", h, r + rope, ctx, seqs, "heads", ("B", "n", k)),
        ("dsa_av", h, ctx, r, seqs, "heads", ("B", "k", k)),
    ]


def _ffn(w: dict, layer: int, T: int) -> list:
    d = w["hidden_size"]
    if layer < w["first_k_dense_replace"]:
        f = w["intermediate_size"]
        return [("ffn_gate_up", T, d, 2 * f, 1, "col", None),
                ("ffn_down", T, f, d, 1, "row", None)]
    e, f = w["n_routed_experts"], w["moe_intermediate_size"]
    rows = max(1, T * w["num_experts_per_tok"] // e)
    s = w["n_shared_experts"]
    return [("moe_router", T, d, e, 1, "none", None),
            ("moe_expert_gate_up", rows, d, 2 * f, e, "col", None),
            ("moe_expert_down", rows, f, d, e, "row", None),
            ("moe_shared_gate_up", T, d, 2 * f, s, "col", None),
            ("moe_shared_down", T, f, d, s, "row", None)]


def decode(w: dict, seq_len: int, batch: int) -> list:
    """Global (unsharded) entries of one decode step, merged."""
    out = []
    for layer in range(w["num_hidden_layers"]):
        out += _decode_layer(w, batch, seq_len, batch) + _ffn(w, layer, batch)
    out.append(("lm_head", batch, w["hidden_size"], w["vocab_size"], 1,
                "col", None))
    merged: dict = {}
    for name, M, K, N, count, tp, sel in out:
        key = (name, M, K, N, tp, sel)
        merged[key] = merged.get(key, 0) + count
    return [(name, M, K, N, count, tp, sel)
            for (name, M, K, N, tp, sel), count in merged.items()]


def _split(size: int, parts: int) -> int:
    return size // parts if size % parts == 0 else size


def per_device(entries: list, mesh: dict) -> list:
    """Per-device entries under a ``{"data": n, "model": n}`` mesh."""
    data, model = mesh["data"], mesh["model"]
    out = []
    for name, M, K, N, count, tp, sel in entries:
        if tp == "heads":
            M, count = _split(M, model), max(1, _split(count, data))
        else:
            M = max(1, _split(M, data))
            if tp == "col":
                N = _split(N, model)
            elif tp == "row":
                K = _split(K, model)
            elif tp == "per_head":
                count = _split(count, model)
        out.append((name, M, K, N, count, tp, sel))
    return out


def densities(M: int, K: int, N: int, sel) -> dict | None:
    """The density specs of an entry's shape: a selection of k of the
    selected rank's L fibres, each as long as the operand's other rank."""
    if sel is None:
        return None
    tensor, rank, k = sel
    extent = {"m": M, "k": K, "n": N}
    other = {"A": {"k": "m", "m": "k"}, "B": {"n": "k", "k": "n"}}[
        tensor][rank]
    L = extent[rank]
    return {tensor: ("selected", {"rank": rank, "L": L, "k": min(k, L),
                                  "fibre": extent[other]})}


@dataclasses.dataclass
class SelectedModel(DensityModel):
    """k of L fibres of ``fibre`` elements hold data, the same k along the
    whole fibre, at uniformly drawn positions."""

    tensor_size: int
    L: int
    k: int
    fibre: int

    @property
    def density(self) -> float:  # type: ignore[override]
        return math.real(self.k) / math.real(self.L)

    def prob_empty(self, tile_size: int) -> float:
        L, k = self.L, self.k
        c = min(math.ceil(tile_size / self.fibre), L)
        if c > L - k:
            return math.real(0.0)
        logp = math.real(0.0)
        for j in range(c):
            logp += math.real(_math.log1p(-k / (L - j)))
        return math.exp(logp)

    def max_nnz(self, tile_size: int) -> int:
        return min(tile_size, self.k * self.fibre)


def dsa_design(rank: str) -> Design:
    """The ``dsa-skip`` option's design for a product whose operand B (the
    latent cache) is selected along ``rank``: the TPU v5e hierarchy, B
    coordinate-payload on that rank and uncompressed on the other
    (18-bit coordinates, for 163840 positions) in HBM and VMEM, skipping
    A's HBM reads and the compute where B is empty."""
    ranks = (RankFormat.CP, RankFormat.U) if rank == "k" else \
        (RankFormat.U, RankFormat.CP)
    fmt = TensorFormat.of(*ranks, coord_bits=18)
    return Design(
        arch=tpu_v5e_arch(),
        safs=SAFSpec(formats={("HBM", "B"): fmt, ("VMEM", "B"): fmt},
                     actions=(ActionSAF(SAFKind.SKIP, "HBM", "A", ("B",)),
                              ActionSAF(SAFKind.SKIP, "compute", "Z",
                                        ("B",)))),
        name=f"tpu-dsa-{rank}")


def design(spec: dict, dens: dict | None) -> Design:
    """The design an option of the configuration file names, for an entry
    of these densities (``dsa-skip`` depends on the selected rank)."""
    if spec["preset"] == "tpu_dsa":
        (rank,) = [arg["rank"] for kind, arg in (dens or {}).values()
                   if kind == "selected"]
        return dsa_design(rank)
    return preset_design(spec)


def evaluate(des: Design, M: int, K: int, N: int, dens: dict | None,
             loops) -> dict:
    """cycles, energy_pj and edp of one mapping (capacity unchecked), the
    density models built here so a selection has one."""
    wl = matmul(M, K, N, densities=dens)
    models = {}
    for t in wl.tensors:
        spec = wl.density_spec(t.name)
        size = t.size(wl.rank_bounds)
        if spec[0] == "selected":
            a = spec[1]
            models[t.name] = SelectedModel(size, int(a["L"]), int(a["k"]),
                                           int(a["fibre"]))
        else:
            models[t.name] = make_density_model(spec, size)
    dense = analyze_dataflow(wl, loops)
    sparse = analyze_sparse(dense, des.safs, des.level_names, models)
    res = evaluate_microarch(des.arch, sparse, check_capacity=False)
    return {"cycles": float(res.cycles), "energy_pj": float(res.energy_pj),
            "edp": float(res.edp)}
