"""Advisor-verdict validation against measured Pallas kernels.

The analytical model predicts *which mechanism* pays on which matmul;
this harness checks the predictions' SIGN against kernels actually
running (interpret mode on CPU, real kernels on TPU), on matmul shapes
drawn from the REDUCED configs.  Three mechanisms, three kinds of
claim — each validated where its effect is actually measurable:

* **skip** (``kernels/block_mm.skip_mm``): block-skipping shortens the
  grid, so the win is wall-clock *even in interpret mode*.  The model
  (SKIP SAFs at the Buffer + compute, bitmask-conditioned on B)
  predicts ~1/density speedup; the measurement is min-of-reps timing of
  the full vs nonzero-block grids.  Sign-gated.
* **gate** (``kernels/block_mm.gated_mm``): gating predicates the MACs
  but walks the full grid — the paper's GATE-saves-energy-not-time
  taxonomy point.  The model (GATE SAFs) predicts ~1.0x time; the
  measurement confirms the *absence* of a wall-clock win, and
  skip-vs-gate ordering confirms skip strictly beats gate.  Sign-gated.
* **N:M** (``kernels/nm_spmm``): on TPU the win is HBM *traffic*
  (decompress-then-dense-MXU) — CPU interpret wall-clock cannot exhibit
  HBM-boundedness, so the sign check is on the measured *weight-bytes
  ratio* of the actually-packed arrays (values + packed offsets vs
  dense), which is what the advisor's verdict monetizes, plus kernel
  correctness against the dense product.  Wall-clock is recorded for
  reference but not sign-gated on CPU.

Shapes are padded up to kernel- and timing-legal sizes (K, N to block
multiples >= ``min_dim``: interpret-mode dispatch overhead swamps the
signal below ~512), and measurement cells are deduplicated globally
across configs, so the whole 10-config harness times a handful of
cells.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import numpy as np

from repro.core.engine import Design, Sparseloop
from repro.core.mapping import LoopNest, nest
from repro.core.presets import dense_design, two_level_arch
from repro.core.taxonomy import (ActionSAF, RankFormat, SAFKind, SAFSpec,
                                 TensorFormat)
from repro.core.workload import matmul

from .extract import extract_network

#: a predicted/measured ratio beyond this is a "win"; the neutral band
#: between 1.0 and the threshold absorbs timing noise
WIN_THRESHOLD = 1.1
#: wider no-win band for the gate arm (gating adds mask-prefetch
#: overhead that can swing interpret-mode timings either way)
GATE_NEUTRAL = 1.25

ALL_ARMS = ("skip-time", "gate-time", "skip-vs-gate",
            "nm-traffic", "nm-correct")
#: arms that are deterministic (no wall-clock) — what unit tests run
DETERMINISTIC_ARMS = ("nm-traffic", "nm-correct")


def edge_mapping(M: int, K: int, N: int, *, ns: int = 16, bm: int = 16,
                 bn: int = 16) -> LoopNest:
    """Structure-stable 2-level mapping (canonical_mapping with unit
    loops KEPT, so every shape shares one bucket/program)."""
    from repro.core.advisor import _div_floor
    bm = _div_floor(M, bm)
    bn = _div_floor(N, bn)
    ns = _div_floor(N // bn, ns)
    return nest(
        2,
        ("m", M // bm, 1), ("n", N // (bn * ns), 1),
        ("n", ns, 1, "spatial"),
        ("n", bn, 0), ("k", K, 0), ("m", bm, 0),
    )


def block_skip_design(arch=None) -> Design:
    """Bitmask-compressed B with SKIP at the Buffer and compute: the
    mechanism skip_mm implements (only nonzero B blocks are visited)."""
    arch = arch or two_level_arch()
    fmts = {("DRAM", "B"): TensorFormat.of(RankFormat.B),
            ("Buffer", "B"): TensorFormat.of(RankFormat.B)}
    actions = (ActionSAF(SAFKind.SKIP, "Buffer", "A", ("B",)),
               ActionSAF(SAFKind.SKIP, "Buffer", "Z", ("B",)),
               ActionSAF(SAFKind.SKIP, "compute", "Z", ("B",)))
    return Design(arch=arch, safs=SAFSpec(formats=fmts, actions=actions),
                  name="block-skip")


def block_gate_design(arch=None) -> Design:
    """Bitmask B with GATE only: MACs are predicated off but the full
    grid is walked — energy savings, no time savings (gated_mm)."""
    arch = arch or two_level_arch()
    fmts = {("DRAM", "B"): TensorFormat.of(RankFormat.B),
            ("Buffer", "B"): TensorFormat.of(RankFormat.B)}
    actions = (ActionSAF(SAFKind.GATE, "Buffer", "A", ("B",)),
               ActionSAF(SAFKind.GATE, "compute", "Z", ("B",)))
    return Design(arch=arch, safs=SAFSpec(formats=fmts, actions=actions),
                  name="block-gate")


@dataclasses.dataclass
class AgreementRow:
    """One (config, arm, cell) sign-agreement check."""

    config: str
    layer: str
    arm: str
    M: int
    K: int
    N: int
    predicted: float
    measured: float
    pred_win: bool
    meas_win: bool
    agree: bool
    detail: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# ----------------------------------------------------------------------
# measurement
# ----------------------------------------------------------------------

def _timeit(fn: Callable, reps: int) -> float:
    """Seconds per call, min over reps (after a compile/warmup call)."""
    out = fn()
    getattr(out, "block_until_ready", lambda: out)()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        getattr(out, "block_until_ready", lambda: out)()
        best = min(best, time.perf_counter() - t0)
    return best


def _pad_to(x: int, mult: int, floor: int) -> int:
    x = max(x, floor)
    return ((x + mult - 1) // mult) * mult


def kernel_cell(M: int, K: int, N: int, *, bs: int = 128,
                min_dim: int = 512) -> tuple[int, int, int]:
    """Pad a model shape up to a kernel- and timing-legal cell: K, N to
    block multiples >= min_dim, M to a multiple of 8 capped at 128 (the
    kernels clamp bm to min(128, M), and one m-tile keeps the grid-size
    signal clean)."""
    Mk = min(128, _pad_to(M, 8, 8))
    return (Mk, _pad_to(K, bs, min_dim), _pad_to(N, bs, min_dim))


def _measure_block_cell(Mk: int, Kk: int, Nk: int, *, density: float,
                        bs: int, reps: int, seed: int = 0,
                        interpret: bool | None = None) -> dict:
    """Wall-clock the skip/gate kernels on one cell (interpret on CPU).

    Returns times for the full grid (dense), the skipped nonzero-block
    grid, and the gated full grid, plus a correctness error."""
    import jax.numpy as jnp
    from repro.kernels.block_mm.ops import (block_indices, block_mm_ref,
                                            gated_mm, skip_mm)
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((Mk, Kk)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((Kk, Nk)), jnp.float32)
    nb_k, nb_n = Kk // bs, Nk // bs
    mask = rng.random((nb_k, nb_n)) < density
    mask[0, :] = True          # every column block present
    mask = np.asarray(mask)
    wm = np.asarray(w).reshape(nb_k, bs, nb_n, bs)
    wm = wm * mask[:, None, :, None]
    wm = jnp.asarray(wm.reshape(Kk, Nk), jnp.float32)
    full = np.ones_like(mask)
    kf, jf = block_indices(full)
    ks, js = block_indices(mask)
    blk = dict(bm=bs, bk=bs, bn=bs, interpret=interpret)
    t_full = _timeit(lambda: skip_mm(a, w, kf, jf, **blk), reps)
    t_skip = _timeit(lambda: skip_mm(a, wm, ks, js, **blk), reps)
    t_gate = _timeit(lambda: gated_mm(a, wm, jnp.asarray(mask), **blk),
                     reps)
    got = skip_mm(a, wm, ks, js, **blk)
    want = block_mm_ref(a, wm, jnp.asarray(mask), bk=bs, bn=bs)
    err = float(jnp.max(jnp.abs(got - want)))
    return {"t_full": t_full, "t_skip": t_skip, "t_gate": t_gate,
            "err": err, "nnzb": int(mask.sum()),
            "blocks": int(mask.size)}


def _measure_nm_cell(Mk: int, Kk: int, Nk: int, *, n: int, m: int,
                     reps: int, bs: int = 128, seed: int = 0,
                     interpret: bool | None = None) -> dict:
    """Pack an N:M-pruned weight and measure what the advisor monetizes:
    the weight-bytes ratio of the real packed arrays, plus kernel
    correctness (and wall-clock, informational on CPU).

    ``bs`` block sizes are passed through to the kernel: cells are
    padded to ``bs`` multiples (``bs`` must be a multiple of ``m``, and
    of 128 for the TPU's kernel compiler)."""
    import jax.numpy as jnp
    from repro.kernels.nm_spmm.ops import nm_spmm
    from repro.sparsity.nm import nm_prune_dense, pack_nm, pack_offsets
    assert bs % m == 0
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((Mk, Kk)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((Kk, Nk)), jnp.float32)
    w_nm = nm_prune_dense(w, n, m)
    vals, idx = pack_nm(w_nm, n, m)
    packed = pack_offsets(idx, m)
    sparse_bytes = vals.nbytes + packed.nbytes
    dense_bytes = w.nbytes
    t_dense = _timeit(lambda: a @ w, reps)
    blk = dict(n=n, m=m, bk=bs, bn=bs, interpret=interpret)
    t_nm = _timeit(lambda: nm_spmm(a, vals, idx, **blk), reps)
    got = nm_spmm(a, vals, idx, **blk)
    want = a @ w_nm
    err = float(jnp.max(jnp.abs(got - want))
                / max(1e-9, float(jnp.max(jnp.abs(want)))))
    return {"bytes_ratio": sparse_bytes / dense_bytes,
            "t_dense": t_dense, "t_nm": t_nm, "err": err}


# ----------------------------------------------------------------------
# the harness
# ----------------------------------------------------------------------

def _predict_block(shapes, *, density: float) -> dict:
    """Model-predicted dense/skip/gate cycles per cell, via the batched
    network path (one program per design)."""
    designs = {"dense": dense_design(two_level_arch()),
               "skip": block_skip_design(),
               "gate": block_gate_design()}
    dens = {"B": ("uniform", density)}
    out: dict = {name: [] for name in designs}
    for name, des in designs.items():
        engine = Sparseloop(des)
        d = None if name == "dense" else dens
        wls = [matmul(M, K, N, densities=d) for M, K, N in shapes]
        nests = [[edge_mapping(M, K, N)] for M, K, N in shapes]
        res = engine.evaluate_network(wls, nests, check_capacity=False)
        out[name] = [float(r["cycles"][0]) for r in res]
    return out


def validate_fleet(config_names=None, *, reduced: bool = True,
                   arms: Sequence[str] = ALL_ARMS,
                   density: float = 0.25, nm: tuple[int, int] = (2, 4),
                   bs: int = 128, min_dim: int = 512, reps: int = 5,
                   max_cells_per_config: int = 2,
                   seq_len: int = 256, batch: int = 8,
                   interpret: bool | None = None
                   ) -> list[AgreementRow]:
    """Run the agreement harness: advisor/model verdict signs vs
    measured kernels on REDUCED-config shapes.  ``bs`` is the kernels'
    block size (a multiple of 128: the TPU refuses narrower blocks);
    ``interpret`` is passed to every kernel call (None: interpret on
    the CPU only).

    Returns one row per (config, arm, cell); a row with
    ``agree=False`` is a modeling claim contradicted by a measurement
    (the CI step fails on any).  Measurement cells are deduped globally
    across configs, so cost scales with unique padded shapes, not
    configs."""
    from repro.configs import ARCH_NAMES, get_config
    from repro.core.advisor import advise
    if config_names is None:
        config_names = ARCH_NAMES
    arms = tuple(arms)

    # ---- collect cells: top weight matmuls per config, padded ----
    per_config: list[tuple[str, str, tuple[int, int, int]]] = []
    for name in config_names:
        cfg = get_config(name, reduced=reduced)
        net = extract_network(cfg, "decode", seq_len=seq_len,
                              batch=batch)
        weights = sorted(net.weight_matmuls(),
                         key=lambda e: e.flops, reverse=True)
        for e in weights[:max_cells_per_config]:
            cell = kernel_cell(e.M, e.K, e.N, bs=bs, min_dim=min_dim)
            per_config.append((cfg.name, e.name, cell))

    cells = sorted({c for _, _, c in per_config})
    block_meas: dict = {}
    nm_meas: dict = {}
    needs_block = any(a in arms for a in
                      ("skip-time", "gate-time", "skip-vs-gate"))
    if needs_block:
        for c in cells:
            block_meas[c] = _measure_block_cell(
                *c, density=density, bs=bs, reps=reps, interpret=interpret)
    if "nm-traffic" in arms or "nm-correct" in arms:
        for c in cells:
            nm_meas[c] = _measure_nm_cell(*c, n=nm[0], m=nm[1],
                                          reps=reps, bs=bs,
                                          interpret=interpret)
    pred = _predict_block(cells, density=density) if needs_block else {}
    cell_ix = {c: i for i, c in enumerate(cells)}

    # ---- advisor N:M verdicts per config (decode-like shard) ----
    nm_pred: dict = {}
    if "nm-traffic" in arms:
        for name in config_names:
            cfg = get_config(name, reduced=reduced)
            adv = advise(cfg, tokens_per_device=batch, tp=1,
                         nm_options=(nm,))
            nm_pred[cfg.name] = {a.layer: a for a in adv}

    rows: list[AgreementRow] = []
    for cfg_name, layer, cell in per_config:
        i = cell_ix[cell]
        M, K, N = cell
        if needs_block:
            bm = block_meas[cell]
            pd, ps, pg = (pred["dense"][i], pred["skip"][i],
                          pred["gate"][i])
            if "skip-time" in arms:
                p, ms = pd / ps, bm["t_full"] / bm["t_skip"]
                pw, mw = p > WIN_THRESHOLD, ms > WIN_THRESHOLD
                rows.append(AgreementRow(
                    cfg_name, layer, "skip-time", M, K, N, p, ms, pw,
                    mw, pw == mw,
                    f"nnzb={bm['nnzb']}/{bm['blocks']} "
                    f"err={bm['err']:.2e}"))
            if "gate-time" in arms:
                p, ms = pd / pg, bm["t_full"] / bm["t_gate"]
                pw, mw = p > WIN_THRESHOLD, ms > GATE_NEUTRAL
                rows.append(AgreementRow(
                    cfg_name, layer, "gate-time", M, K, N, p, ms, pw,
                    mw, pw == mw,
                    "gate walks the full grid: no time win"))
            if "skip-vs-gate" in arms:
                p, ms = pg / ps, bm["t_gate"] / bm["t_skip"]
                pw, mw = p > WIN_THRESHOLD, ms > WIN_THRESHOLD
                rows.append(AgreementRow(
                    cfg_name, layer, "skip-vs-gate", M, K, N, p, ms,
                    pw, mw, pw == mw,
                    "SKIP saves time over GATE (taxonomy ordering)"))
        if "nm-traffic" in arms and cell in nm_meas:
            nmm = nm_meas[cell]
            adv = nm_pred.get(cfg_name, {}).get(layer)
            p = adv.speedup if adv else 1.0
            ms = 1.0 / nmm["bytes_ratio"]
            # the advisor only claims a win when compressed traffic is
            # lower; measured packed bytes must agree in sign
            pw, mw = p > 1.0 + 1e-6, ms > 1.0 + 1e-6
            rows.append(AgreementRow(
                cfg_name, layer, "nm-traffic", M, K, N, p, ms, pw, mw,
                (not pw) or mw,
                f"bytes_ratio={nmm['bytes_ratio']:.4f} "
                f"t_nm/t_dense={nmm['t_nm'] / nmm['t_dense']:.2f} "
                "(wall-clock informational on CPU)"))
        if "nm-correct" in arms and cell in nm_meas:
            err = nm_meas[cell]["err"]
            ok = err < 1e-3
            rows.append(AgreementRow(
                cfg_name, layer, "nm-correct", M, K, N, 0.0, err, ok,
                ok, ok, "kernel output vs dense product of pruned W"))
    return rows


def agreement_summary(rows: Sequence[AgreementRow]) -> str:
    bad = [r for r in rows if not r.agree]
    by_arm: dict = {}
    for r in rows:
        by_arm.setdefault(r.arm, []).append(r)
    lines = [f"advisor agreement: {len(rows) - len(bad)}/{len(rows)} "
             f"rows agree across {len(by_arm)} arms"]
    for arm, rs in sorted(by_arm.items()):
        ag = sum(1 for r in rs if r.agree)
        preds = ", ".join(f"{r.predicted:.2f}/{r.measured:.2f}"
                          for r in rs[:3])
        lines.append(f"  {arm:>14}: {ag}/{len(rs)} agree "
                     f"(pred/meas e.g. {preds})")
    for r in bad:
        lines.append(f"  DISAGREE {r.config} {r.layer} {r.arm} "
                     f"M{r.M} K{r.K} N{r.N}: predicted {r.predicted:.3f}"
                     f" measured {r.measured:.3f} ({r.detail})")
    return "\n".join(lines)
