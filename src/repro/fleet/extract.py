"""Fleet workload extraction: every LM config -> per-layer matmuls.

Walks a :class:`repro.models.ModelConfig` (any of the 10 families in
``repro/configs/``: dense GQA decoders, MoE, MLA, encoder-decoder,
xLSTM, Mamba2 hybrids) and emits the matmul workloads one forward pass
executes, for a *prefill* (all sequence positions) or *decode* (one
token per sequence) phase.  Two invariants make the extraction
trustworthy rather than approximate, and tests pin both exactly:

* **parameter exactness** — summing ``K*N*param_instances`` over the
  prefill entries (plus the embedding table) reproduces
  ``ModelConfig.param_count()`` to the parameter, for every CONFIG and
  REDUCED config, because the walk mirrors ``param_count``'s per-layer
  branch structure rather than re-deriving shapes independently;
* **FLOP exactness** — ``2*M*K*N*count`` summed over entries matches
  closed-form per-family FLOP counts for both phases.

Repeated layers collapse at extraction time: the merge step keys on
``(name, M, K, N)`` so the 36 identical attention blocks of qwen3-4b
become ONE entry with ``count=36`` — the evaluation-side dedup
(`fleet.sweep.dedupe_shapes`) then collapses shape collisions *across*
entries and configs.

A model with DeepSeek Sparse Attention (``cfg.dsa``) decodes in MLA's
absorbed form: ``kv_b`` splits into per-head W_UK and W_UV, every head
scores the one shared 576-wide latent cache (heads in M), and the score
and value products carry the indexer's selection of ``index_topk`` of
the cache's tokens as a density (``LayerMatmul.select``); the indexer
itself scores the whole cache, dense.  Prefill keeps MLA's
non-absorbed form with dense attention.

Sharding reuses the production resolver: ``shard_entries`` maps each
entry to its per-device shape under ``launch.sharding.resolve_spec``
(Megatron-style: column-parallel QKV/up projections split N on
"model", row-parallel out projections split K, token dims split on the
data axes, attention heads split on "model"; indivisible axes
replicate, exactly as the real launcher would).  :class:`MeshSpec` is a
topology-only stand-in for a jax Mesh — same duck type
(``.shape``/``.axis_names``), no device allocation — so extraction
works on a laptop with no 256-chip mesh and under the CI jax floor.
"""
from __future__ import annotations

import dataclasses

from jax.sharding import PartitionSpec as P

from repro.launch.mesh import production_mesh_shape
from repro.launch.sharding import _axis_size, resolve_spec


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Topology-only mesh: satisfies the ``.shape[axis]`` /
    ``.axis_names`` duck type that ``resolve_spec`` consumes, without
    materializing devices."""

    axes: tuple[tuple[str, int], ...]

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    @property
    def shape(self) -> dict:
        return dict(self.axes)

    @property
    def size(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n


def production_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    """The production mesh's topology (16x16 data*model per pod)."""
    return MeshSpec(production_mesh_shape(multi_pod=multi_pod))


@dataclasses.dataclass(frozen=True)
class LayerMatmul:
    """One matmul shape a forward pass executes.

    ``count`` is how many times the shape runs per forward (e.g. once
    per layer, per head, per expert); ``param_instances`` is how many
    distinct K*N weight matrices it materializes (0 for
    activation-activation products like attention scores — their
    operands are produced, not stored).  ``tp`` tags the tensor-parallel
    style used by ``shard_entries``: "col" splits N, "row" splits K,
    "none" replicates the weight, "per_head" splits the per-head
    ``count``, and "heads" marks an activation product with the heads
    in M (split like heads, its sequences in ``count``).

    ``select`` is ``(tensor, rank, k)`` for a product whose operand
    ``tensor`` ("A" or "B") holds only ``k`` of its ``rank`` extent's
    fibres (a top-k selection); :attr:`densities` states it for the
    entry's current shape.
    """

    name: str
    M: int
    K: int
    N: int
    count: int = 1
    param_instances: int = 1
    tp: str = "none"
    select: tuple[str, str, int] | None = None

    @property
    def shape(self) -> tuple[int, int, int]:
        return (self.M, self.K, self.N)

    @property
    def densities(self) -> dict | None:
        """Workload density specs of the entry (None: dense): a
        selection of k of the selected rank's L fibres, each as long as
        the operand's other rank (``rank`` names the selected rank)."""
        if self.select is None:
            return None
        tensor, rank, k = self.select
        extent = {"m": self.M, "k": self.K, "n": self.N}
        other, = set({"A": "mk", "B": "kn"}[tensor]) - {rank}
        L = extent[rank]
        return {tensor: ("selected", {"rank": rank, "L": L,
                                      "k": min(k, L),
                                      "fibre": extent[other]})}

    @property
    def weight_params(self) -> int:
        return self.K * self.N * self.param_instances

    @property
    def flops(self) -> int:
        return 2 * self.M * self.K * self.N * self.count


@dataclasses.dataclass(frozen=True)
class NetworkWorkloads:
    """All matmuls of one (config, phase), merged across identical
    layers.  ``extra_params`` carries non-matmul weights (the embedding
    lookup table)."""

    config: str
    phase: str
    matmuls: tuple[LayerMatmul, ...]
    extra_params: int = 0

    def weight_matmuls(self) -> tuple[LayerMatmul, ...]:
        return tuple(e for e in self.matmuls if e.param_instances > 0)

    def attention_matmuls(self) -> tuple[LayerMatmul, ...]:
        return tuple(e for e in self.matmuls if e.param_instances == 0)

    @property
    def total_params(self) -> int:
        """Exact parameter count (== cfg.param_count() for prefill,
        which touches every weight; decode skips encoder weights)."""
        return self.extra_params + sum(
            e.weight_params for e in self.matmuls)

    @property
    def total_flops(self) -> int:
        return sum(e.flops for e in self.matmuls)


# ----------------------------------------------------------------------
# extraction walk (mirrors ModelConfig.param_count branch-for-branch)
# ----------------------------------------------------------------------

def _mla_q(cfg, T: int) -> list[LayerMatmul]:
    """MLA's query: one projection, or q-LoRA's down and up."""
    m, d = cfg.mla, cfg.d_model
    n = cfg.num_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    if not m.q_lora_rank:
        return [LayerMatmul("mla_q_proj", T, d, n, tp="col")]
    return [LayerMatmul("mla_q_a", T, d, m.q_lora_rank, tp="none"),
            LayerMatmul("mla_q_b", T, m.q_lora_rank, n, tp="col")]


def _indexer_weights(cfg, T: int) -> list[LayerMatmul]:
    """DSA's lightning indexer: per-head queries from the query latent,
    one shared key and a per-head weight from the hidden state."""
    m, x, d = cfg.mla, cfg.dsa, cfg.d_model
    return [
        LayerMatmul("idx_q", T, m.q_lora_rank or d,
                    x.index_n_heads * x.index_head_dim, tp="col"),
        LayerMatmul("idx_k", T, d, x.index_head_dim, tp="none"),
        LayerMatmul("idx_w", T, d, x.index_n_heads, tp="none"),
    ]


def _dsa_decode(cfg, T: int, kv_len: int, n_seq: int) -> list[LayerMatmul]:
    """One DSA layer's decode step, MLA absorbed: W_UK folds into the
    query and W_UV into the output, so every head attends over the
    shared latent cache (``kv_lora_rank`` + rope wide), on the indexer's
    ``index_topk`` selected tokens of the ``kv_len`` cached.  The cache
    is operand B of both products, selected along L: its columns in the
    score product, its rows in the value product (whose probabilities,
    A, stay dense over the cache)."""
    m, x, d, h = cfg.mla, cfg.dsa, cfg.d_model, cfg.num_heads
    latent = m.kv_lora_rank + m.qk_rope_head_dim
    k = x.index_topk
    return _mla_q(cfg, T) + [
        LayerMatmul("mla_kv_a", T, d, latent, tp="none"),
        LayerMatmul("mla_uk", T, m.qk_nope_head_dim, m.kv_lora_rank,
                    count=h, param_instances=h, tp="per_head"),
        LayerMatmul("mla_uv", T, m.kv_lora_rank, m.v_head_dim,
                    count=h, param_instances=h, tp="per_head"),
        LayerMatmul("mla_o", T, h * m.v_head_dim, d, tp="row"),
    ] + _indexer_weights(cfg, T) + [
        LayerMatmul("idx_scores", x.index_n_heads, x.index_head_dim,
                    kv_len, count=n_seq, param_instances=0, tp="heads"),
        LayerMatmul("dsa_qk", h, latent, kv_len, count=n_seq,
                    param_instances=0, tp="heads", select=("B", "n", k)),
        LayerMatmul("dsa_av", h, kv_len, m.kv_lora_rank, count=n_seq,
                    param_instances=0, tp="heads", select=("B", "k", k)),
    ]


def _attn_weights(cfg, T: int) -> list[LayerMatmul]:
    d = cfg.d_model
    if cfg.mla:
        m = cfg.mla
        h = cfg.num_heads
        out = _mla_q(cfg, T) + [
            LayerMatmul("mla_kv_a_proj", T, d,
                        m.kv_lora_rank + m.qk_rope_head_dim, tp="none"),
            LayerMatmul("mla_kv_b_proj", T, m.kv_lora_rank,
                        h * (m.qk_nope_head_dim + m.v_head_dim),
                        tp="col"),
            LayerMatmul("mla_o_proj", T, h * m.v_head_dim, d, tp="row"),
        ]
        return out + (_indexer_weights(cfg, T) if cfg.dsa else [])
    return [
        LayerMatmul("attn_qkv", T, d, cfg.q_dim + 2 * cfg.kv_dim,
                    tp="col"),
        LayerMatmul("attn_o_proj", T, cfg.q_dim, d, tp="row"),
    ]


def _attn_scores(cfg, prefix: str, q_len: int, kv_len: int,
                 n_seq: int, layer_count: int = 1) -> list[LayerMatmul]:
    if cfg.mla:
        qk_dim = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
        v_dim = cfg.mla.v_head_dim
    else:
        qk_dim = v_dim = cfg.head_dim
    count = cfg.num_heads * n_seq * layer_count
    return [
        LayerMatmul(f"{prefix}_qk", q_len, qk_dim, kv_len,
                    count=count, param_instances=0),
        LayerMatmul(f"{prefix}_av", q_len, kv_len, v_dim,
                    count=count, param_instances=0),
    ]


def _ffn_weights(cfg, layer: int, T: int) -> list[LayerMatmul]:
    d = cfg.d_model
    out = []
    if cfg.is_moe_layer(layer):
        m = cfg.moe
        tok = max(1, (T * m.top_k) // m.num_experts)
        out.append(LayerMatmul("moe_router", T, d, m.num_experts,
                               tp="none"))
        out.append(LayerMatmul("moe_expert_gate_up", tok, d,
                               2 * m.expert_d_ff,
                               count=m.num_experts,
                               param_instances=m.num_experts, tp="col"))
        out.append(LayerMatmul("moe_expert_down", tok, m.expert_d_ff, d,
                               count=m.num_experts,
                               param_instances=m.num_experts, tp="row"))
        if m.num_shared_experts:
            out.append(LayerMatmul(
                "moe_shared_gate_up", T, d, 2 * m.shared_d_ff,
                count=m.num_shared_experts,
                param_instances=m.num_shared_experts, tp="col"))
            out.append(LayerMatmul(
                "moe_shared_down", T, m.shared_d_ff, d,
                count=m.num_shared_experts,
                param_instances=m.num_shared_experts, tp="row"))
    elif cfg.d_ff:
        out.append(LayerMatmul("ffn_gate_up", T, d, 2 * cfg.d_ff,
                               tp="col"))
        out.append(LayerMatmul("ffn_down", T, cfg.d_ff, d, tp="row"))
    return out


def _merge(entries: list[LayerMatmul]) -> tuple[LayerMatmul, ...]:
    """Collapse per-layer duplicates: same (name, M, K, N) becomes one
    entry with summed count / param_instances."""
    merged: dict = {}
    order = []
    for e in entries:
        key = (e.name, e.M, e.K, e.N, e.tp, e.select)
        if key in merged:
            old = merged[key]
            merged[key] = dataclasses.replace(
                old, count=old.count + e.count,
                param_instances=old.param_instances + e.param_instances)
        else:
            merged[key] = e
            order.append(key)
    return tuple(merged[k] for k in order)


def extract_network(cfg, phase: str = "prefill", *,
                    seq_len: int = 4096, batch: int | None = None,
                    ctx_len: int | None = None,
                    enc_len: int = 1500) -> NetworkWorkloads:
    """Emit the matmuls of one forward pass.

    prefill: every sequence position is live (T = batch * seq tokens,
    attention is q_len=seq vs kv_len=seq).  decode: one new token per
    sequence (T = batch tokens, attention is q_len=1 vs the kv cache of
    ``ctx_len`` positions).  ``attn_window`` caps kv_len in both.  A
    DSA model decodes in MLA's absorbed form (module docstring); at
    prefill its indexer scores every query against every key, and its
    attention is the dense product (the selection, which differs per
    query row there, is not modelled).
    Returns GLOBAL (unsharded) shapes; apply :func:`shard_entries` for
    per-device shapes.
    """
    if phase not in ("prefill", "decode"):
        raise ValueError(f"phase must be prefill|decode, got {phase!r}")
    if batch is None:
        batch = 16 if phase == "prefill" else 256
    d = cfg.d_model
    dec_seq = min(seq_len, cfg.dec_max_len) if cfg.enc_dec else seq_len
    ctx = min(ctx_len or dec_seq, cfg.dec_max_len) if cfg.enc_dec \
        else (ctx_len or seq_len)
    if phase == "prefill":
        q_len, kv_len, T = dec_seq, dec_seq, dec_seq * batch
    else:
        q_len, kv_len, T = 1, ctx, batch
    if cfg.attn_window:
        kv_len = min(kv_len, cfg.attn_window)

    entries: list[LayerMatmul] = []
    for layer in range(cfg.num_layers):
        kind = cfg.block_kind(layer)
        if kind == "attn" and cfg.dsa and phase == "decode":
            entries += _dsa_decode(cfg, T, kv_len, batch)
        elif kind == "attn":
            entries += _attn_weights(cfg, T)
            if cfg.dsa:
                x = cfg.dsa
                entries.append(LayerMatmul(
                    "idx_scores", q_len, x.index_head_dim, kv_len,
                    count=x.index_n_heads * batch, param_instances=0))
            entries += _attn_scores(cfg, "attn", q_len, kv_len, batch)
        elif kind == "mamba2":
            di = cfg.ssm_expand * d
            entries += [
                LayerMatmul("ssm_in_proj", T, d, 2 * di, tp="col"),
                LayerMatmul("ssm_out_proj", T, di, d, tp="row"),
                LayerMatmul("ssm_bcdt_proj", T, di,
                            2 * cfg.ssm_state + 3, tp="none"),
            ]
        else:  # xlstm blocks (mlstm / slstm)
            di = cfg.ssm_expand * d
            entries += [
                LayerMatmul(f"{kind}_up_proj", T, d, 2 * di, tp="col"),
                LayerMatmul(f"{kind}_down_proj", T, di, d, tp="row"),
            ]
        if kind == "attn" or cfg.family not in ("ssm",):
            entries += _ffn_weights(cfg, layer, T)

    if cfg.hybrid and cfg.hybrid.shared_attn_d_ff:
        # one SHARED attention block applied num_layers // period times:
        # weights materialize once (param_instances stays 1 per matmul),
        # compute repeats per application
        apps = cfg.num_layers // cfg.hybrid.period
        sd = cfg.hybrid.shared_attn_d_ff
        entries += [
            LayerMatmul("shared_attn_qkv", T, d,
                        cfg.q_dim + 2 * cfg.kv_dim, count=apps, tp="col"),
            LayerMatmul("shared_attn_o_proj", T, cfg.q_dim, d,
                        count=apps, tp="row"),
            LayerMatmul("shared_ffn_gate_up", T, d, 2 * sd,
                        count=apps, tp="col"),
            LayerMatmul("shared_ffn_down", T, sd, d,
                        count=apps, tp="row"),
        ]
        entries += _attn_scores(cfg, "shared_attn", q_len, kv_len,
                                batch, layer_count=apps)

    if cfg.enc_dec:
        T_enc = enc_len * batch
        if phase == "prefill":
            # encoder runs once, at prefill
            entries += [
                LayerMatmul("enc_qkv", T_enc, d, 3 * d,
                            count=cfg.enc_layers,
                            param_instances=cfg.enc_layers, tp="col"),
                LayerMatmul("enc_o_proj", T_enc, d, d,
                            count=cfg.enc_layers,
                            param_instances=cfg.enc_layers, tp="row"),
                LayerMatmul("enc_ffn_gate_up", T_enc, d, 2 * cfg.d_ff,
                            count=cfg.enc_layers,
                            param_instances=cfg.enc_layers, tp="col"),
                LayerMatmul("enc_ffn_down", T_enc, cfg.d_ff, d,
                            count=cfg.enc_layers,
                            param_instances=cfg.enc_layers, tp="row"),
            ]
            entries += _attn_scores(cfg, "enc_attn", enc_len, enc_len,
                                    batch, layer_count=cfg.enc_layers)
            # cross-attention K/V projections over encoder memory run
            # once at prefill and are cached for decode
            entries += [
                LayerMatmul("cross_k_proj", T_enc, d, d,
                            count=cfg.num_layers,
                            param_instances=cfg.num_layers, tp="col"),
                LayerMatmul("cross_v_proj", T_enc, d, d,
                            count=cfg.num_layers,
                            param_instances=cfg.num_layers, tp="col"),
            ]
        # cross-attention Q/O run per decoder step in both phases
        entries += [
            LayerMatmul("cross_q_proj", T, d, d, count=cfg.num_layers,
                        param_instances=cfg.num_layers, tp="col"),
            LayerMatmul("cross_o_proj", T, d, d, count=cfg.num_layers,
                        param_instances=cfg.num_layers, tp="row"),
        ]
        entries += _attn_scores(cfg, "cross_attn", q_len, enc_len,
                                batch, layer_count=cfg.num_layers)

    entries.append(LayerMatmul("lm_head", T, d, cfg.vocab_size,
                               tp="col"))
    # embedding table: a lookup, not a matmul (tied -> lm_head weight)
    extra = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    return NetworkWorkloads(config=cfg.name, phase=phase,
                            matmuls=_merge(entries), extra_params=extra)


# ----------------------------------------------------------------------
# production sharding
# ----------------------------------------------------------------------

def _shard_dim(size: int, axis, mesh) -> int:
    if mesh is None or axis is None:
        return size
    spec = resolve_spec(P(axis), (size,), mesh)
    entry = spec[0] if len(spec) else None
    return size // _axis_size(mesh, entry)


def shard_entries(net: NetworkWorkloads, mesh) -> NetworkWorkloads:
    """Per-device shapes under ``mesh`` (a jax Mesh or MeshSpec).

    Token dims (M) split over the data axes; "col" weights split N and
    "row" weights split K over "model", "per_head" weights their head
    count; attention score counts split heads over "model" and
    sequences over data, and "heads" products split their M (the heads)
    over "model" and their count (the sequences) over data.  Indivisible splits
    replicate (resolve_spec semantics) — shapes never go fractional.
    """
    if mesh is None:
        return net
    out = []
    for e in net.matmuls:
        if e.tp == "heads":
            # heads in M on "model", the sequences in count on data
            out.append(dataclasses.replace(
                e, M=_shard_dim(e.M, "model", mesh),
                count=max(1, _shard_dim(e.count, "data", mesh))))
            continue
        if e.param_instances == 0:
            # count = heads * n_seq * layers; shard the head product on
            # "model" and the sequence product on the data axes
            count = _shard_dim(e.count, "model", mesh)
            count = _shard_dim(count, "data", mesh)
            out.append(dataclasses.replace(e, count=max(1, count)))
            continue
        M = max(1, _shard_dim(e.M, "data", mesh))
        K, N = e.K, e.N
        count = e.count
        if e.tp == "col":
            N = _shard_dim(N, "model", mesh)
        elif e.tp == "row":
            K = _shard_dim(K, "model", mesh)
        elif e.tp == "per_head":
            count = _shard_dim(count, "model", mesh)
        out.append(dataclasses.replace(e, M=M, K=K, N=N, count=count))
    return dataclasses.replace(net, matmuls=tuple(out))


def extract_fleet(config_names, *, reduced: bool = False,
                  phases=("prefill", "decode"), mesh=None,
                  seq_len: int = 4096,
                  batch: int | None = None) -> list[NetworkWorkloads]:
    """Extract (and optionally shard) every (config, phase) of a fleet."""
    from repro.configs import get_config
    nets = []
    for name in config_names:
        cfg = get_config(name, reduced=reduced)
        for phase in phases:
            net = extract_network(cfg, phase, seq_len=seq_len,
                                  batch=batch)
            nets.append(shard_entries(net, mesh))
    return nets
