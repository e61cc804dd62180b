"""Fleet sweep driver: every config x sparsity option through shared
compiled programs.

This is the paper's DSE loop (Sec. 7) scaled from one accelerator and a
handful of workloads to the whole model fleet: every per-layer matmul of
every ``repro/configs/`` architecture, prefill and decode, dense vs each
N:M compression option, evaluated through
``Sparseloop.evaluate_rows`` so the entire sweep costs O(#options x
#buckets) XLA compiles — *independent of config count, layer count, and
phase count*.  Three structural facts make that bound hold, and
:func:`compile_bound` computes it from them up front so CI can gate on
``compiles <= bound``:

* ``advisor.tpu_mapping`` keeps unit-bound loops, so every matmul shape
  in the fleet lowers into ONE padded-template bucket per design;
* workload rank bounds and density parameters are traced inputs, one
  row of them per shape, so different shapes bind the same program —
  and share its calls, ``batched.ROW_BLOCK`` shapes to a call, each
  call of the one block shape;
* uniform/structured density models need no static capacity padding
  (``DensityCaps(0,0,0)``), so *separate* ``evaluate_rows`` calls —
  crossover grids, repeat sweeps, subset sweeps — still share programs.

Identical shapes are deduplicated before evaluation (`dedupe_shapes`):
the fleet's ~hundreds of per-layer entries collapse to the unique
(M, K, N, densities) set, each evaluated once and fanned back out; the
avoided evaluations are counted in ``compile_stats.dedup_evals``.

Entries that carry a selection (DeepSeek Sparse Attention's score and
value products, ``LayerMatmul.select``) are evaluated with it under
every option that applies to them: ``dense`` pays for the whole cache
(masked-dense attention), and ``dsa-skip`` — an option only for them —
gathers the selected cache tokens by their coordinates and skips the
rest.  With ``ctx_grid`` the sweep also finds, per selected entry, the
smallest cache length at which skipping beats dense
(``FleetReport.ctx_crossover``).
A sweep without such entries runs exactly as before.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Sequence

from repro import obs
from repro.core import compile_stats
from repro.core.advisor import tpu_mapping
from repro.core.engine import Design, Sparseloop
from repro.core.presets import (dense_design, tpu_dsa_design, tpu_nm_design,
                                tpu_v5e_arch)
from repro.core.workload import matmul

from .extract import (LayerMatmul, NetworkWorkloads, extract_fleet,
                      production_mesh_spec)

_EPS = 1e-9
#: a compression option must beat dense by this factor to win (ties and
#: numerical noise stay "dense")
WIN_MARGIN = 1.002


def nm_design_for_weights(n: int, m: int) -> Design:
    """The TPU N:M preset with its compression formats remapped from
    tensor A to tensor B — in the einsum convention here A is the (M,K)
    activation and B the (K,N) weight, and N:M pruning targets
    weights."""
    des = tpu_nm_design(n, m)
    fmts = {(lvl, "B"): f
            for (lvl, _t), f in des.safs.formats.items()}
    return Design(arch=des.arch,
                  safs=dataclasses.replace(des.safs, formats=fmts),
                  name=des.name)


@dataclasses.dataclass(frozen=True)
class SweepOption:
    """One design point of the sweep portfolio.  ``design`` evaluates
    every entry the option applies to; given as a dict, selected rank ->
    design, the option applies only to entries that carry a selection,
    each on the design for its selected rank."""

    name: str
    design: Design | dict
    #: densities dict applied to each workload (None = dense)
    densities: dict | None = None
    #: only meaningful for weight matmuls (param_instances > 0)?
    weights_only: bool = False

    @property
    def selected_only(self) -> bool:
        return isinstance(self.design, dict)

    def applies_to(self, e: LayerMatmul) -> bool:
        if isinstance(self.design, dict):
            return e.select is not None
        return e.param_instances > 0 or not self.weights_only

    def design_for(self, densities: dict | None) -> Design:
        """The design of a row with these (its entry's) densities."""
        if not isinstance(self.design, dict):
            return self.design
        rank, = (arg["rank"] for kind, arg in densities.values()
                 if kind == "selected")
        return self.design[rank]

    def designs(self) -> list[Design]:
        return (list(self.design.values()) if self.selected_only
                else [self.design])


def dense_option() -> SweepOption:
    return SweepOption("dense", dense_design(tpu_v5e_arch()))


def nm_option(n: int, m: int) -> SweepOption:
    return SweepOption(f"nm-{n}:{m}", nm_design_for_weights(n, m),
                       densities={"B": ("structured", {"n": n, "m": m})},
                       weights_only=True)


def dsa_option() -> SweepOption:
    """Gather the cache tokens a DSA selection keeps and skip the rest
    (:func:`~repro.core.presets.tpu_dsa_design` for the cache's selected
    rank); applies to selected entries only."""
    return SweepOption("dsa-skip", {r: tpu_dsa_design(r) for r in "kn"})


def default_options(nm_options=((2, 4), (2, 8))) -> list[SweepOption]:
    return [dense_option()] + [nm_option(n, m) for n, m in nm_options]


# ----------------------------------------------------------------------
# dedup
# ----------------------------------------------------------------------

def dedupe_shapes(entries: Sequence[LayerMatmul]) -> tuple[list, list[int]]:
    """Collapse entries to unique (M, K, N, densities) rows.

    Returns ``(unique, index)`` with ``unique[index[i]] ==
    (*entries[i].shape, entries[i].densities)`` — evaluate each unique
    row once, fan results back out through ``index``."""
    unique: list = []
    where: dict = {}
    index = []
    for e in entries:
        # an entry's densities follow from its shape and selection
        key = (e.shape, e.select)
        if key not in where:
            where[key] = len(unique)
            unique.append((*e.shape, e.densities))
        index.append(where[key])
    return unique, index


def _evaluate_shapes(option: SweepOption, rows, *,
                     check_capacity: bool = False) -> list[dict]:
    """One result dict per ``(M, K, N, densities)`` row (the entry's
    densities; the option adds its own), via the batched row path: the
    rows of each design in a few calls of one program."""
    if not rows:
        return []
    if option.selected_only:
        groups: dict = {}
        for i, row in enumerate(rows):
            design = option.design_for(row[3])
            groups.setdefault(id(design), (design, []))[1].append(i)
        groups = groups.values()
    else:
        groups = [(option.design, range(len(rows)))]
    out: list = [None] * len(rows)
    for design, idxs in groups:
        workloads, nests = [], []
        for i in idxs:
            M, K, N, dens = rows[i]
            workloads.append(matmul(M, K, N, densities=(
                option.densities if dens is None
                else {**dens, **(option.densities or {})})))
            nests.append(tpu_mapping(M, K, N))
        outs = Sparseloop(design).evaluate_rows(
            workloads, nests, check_capacity=check_capacity)
        for i, o in zip(idxs, outs):
            out[i] = {"cycles": float(o["cycles"]),
                      "energy_pj": float(o["energy_pj"]),
                      "edp": float(o["edp"])}
    return out


def compile_bound(options: Sequence[SweepOption], entries,
                  *, check_capacity: bool = False,
                  crossover: bool = False) -> int:
    """The sweep's compile budget, from structure alone: one program per
    (design, bucket, whether the call's rows hold a selection) over the
    calls the sweep makes — each design's rows of every option and, with
    ``crossover``, each option's weight rows again (which hold none).
    tpu_mapping's structure-stable nests put any shape mix in 1 bucket
    per design, so a sweep without selections is bounded by its number
    of design points, independent of configs/layers."""
    from repro.core.batched import bucket_for, template_of
    del check_capacity
    ranks = tuple(matmul(2, 2, 2).rank_bounds)
    bucket_of: dict = {}

    def bucket(e):
        if e.shape not in bucket_of:
            bucket_of[e.shape] = bucket_for(
                template_of(tpu_mapping(*e.shape)), ranks)
        return bucket_of[e.shape]

    programs = set()
    for opt in options:
        buckets: dict = {}      # design id -> the buckets of its call
        selected = set()        # design ids whose call holds a selection
        for e in entries:
            if opt.applies_to(e):
                d = id(opt.design_for(e.densities))
                buckets.setdefault(d, set()).add(bucket(e))
                if e.select is not None:
                    selected.add(d)
        programs.update((d, b, d in selected)
                        for d, bs in buckets.items() for b in bs)
        if crossover and not opt.selected_only:
            # the M-grid call: weight rows, which hold no selection
            programs.update((id(opt.design), bucket(e), False)
                            for e in entries if e.param_instances > 0)
    return len(programs)


# ----------------------------------------------------------------------
# report
# ----------------------------------------------------------------------

@dataclasses.dataclass
class LayerVerdict:
    """Per-(config, phase, layer-entry) advisor verdict."""

    config: str
    phase: str
    layer: str
    M: int
    K: int
    N: int
    count: int
    dense_cycles: float
    dense_energy_pj: float
    best_option: str
    best_cycles: float
    best_energy_ratio: float
    #: option name -> {cycles, energy_pj, edp}
    options: dict = dataclasses.field(default_factory=dict)
    #: the entry's own density specs (a selection), None when dense
    densities: dict | None = None

    @property
    def speedup(self) -> float:
        return self.dense_cycles / max(_EPS, self.best_cycles)

    @property
    def verdict(self) -> str:
        """"compress" when some option beats dense past WIN_MARGIN."""
        return "compress" if self.best_option != "dense" else "dense"

    @property
    def predicted_edp(self) -> float:
        return self.options.get(self.best_option, {}).get(
            "edp", self.dense_cycles * self.dense_energy_pj)


@dataclasses.dataclass
class FleetReport:
    """Fleet-wide sweep result + the compile accounting that CI gates."""

    rows: list[LayerVerdict]
    option_names: tuple[str, ...]
    #: "KxN" -> {option: largest M on the grid where compression still
    #: wins (the compress-vs-dense crossover), None if it never wins}
    crossover: dict = dataclasses.field(default_factory=dict)
    #: "<layer>:<M>x<K>x<N>" of each selected entry, its selected rank
    #: written "L" -> {option: smallest cache length on the ctx grid at
    #: which skipping the unselected fibres beats dense, None if never}
    ctx_crossover: dict = dataclasses.field(default_factory=dict)
    stats: dict = dataclasses.field(default_factory=dict)
    compile_bound: int = 0
    unique_shapes: int = 0
    total_entries: int = 0
    total_flops: float = 0.0
    total_dense_computes: float = 0.0
    wall_seconds: float = 0.0

    @property
    def compile_seconds(self) -> float:
        return float(self.stats.get("compile_seconds", 0.0))

    @property
    def eval_seconds(self) -> float:
        return float(self.stats.get("eval_seconds", 0.0))

    def summary(self) -> str:
        wins = sum(1 for r in self.rows if r.verdict == "compress")
        evals = (self.stats.get("batched_evals", 0)
                 + self.stats.get("dedup_evals", 0))
        lines = [
            f"fleet sweep: {self.total_entries} layer entries "
            f"({self.unique_shapes} unique shapes) x "
            f"{len(self.option_names)} options",
            f"  compiles {self.stats.get('compiles', '?')} "
            f"(bound {self.compile_bound}), "
            f"program shares {self.stats.get('program_shares', '?')}, "
            f"dedup-avoided evals {self.stats.get('dedup_evals', '?')}, "
            f"scalar evals {self.stats.get('scalar_evals', '?')}",
            f"  wall {self.wall_seconds:.2f} s: "
            f"{self.stats.get('compiles', 0)} compiles took "
            f"{self.compile_seconds:.2f} s, {evals} evals "
            f"({self.stats.get('dedup_evals', 0)} dedup'd) took "
            f"{self.eval_seconds:.2f} s",
            f"  verdicts: {wins} compress / "
            f"{len(self.rows) - wins} dense",
        ]
        return "\n".join(lines)

    def to_json(self) -> dict:
        return {
            "option_names": list(self.option_names),
            "compile_bound": self.compile_bound,
            "unique_shapes": self.unique_shapes,
            "total_entries": self.total_entries,
            "total_flops": self.total_flops,
            "total_dense_computes": self.total_dense_computes,
            "wall_seconds": self.wall_seconds,
            "stats": dict(self.stats),
            "crossover": {k: dict(v) for k, v in self.crossover.items()},
            "ctx_crossover": {k: dict(v)
                              for k, v in self.ctx_crossover.items()},
            "rows": [dict(dataclasses.asdict(r),
                          speedup=r.speedup, verdict=r.verdict)
                     for r in self.rows],
        }

    def dumps(self) -> str:
        return json.dumps(self.to_json(), indent=1, sort_keys=True)


# ----------------------------------------------------------------------
# the sweep
# ----------------------------------------------------------------------

def fleet_sweep(config_names=None, *, reduced: bool = False,
                phases=("prefill", "decode"),
                nm_options=((2, 4), (2, 8)),
                options: Sequence[SweepOption] | None = None,
                mesh="production", seq_len: int = 4096,
                batch: int | None = None,
                include_attention: bool = True,
                crossover: bool = False,
                crossover_grid=(8, 64, 512, 4096, 32768),
                ctx_grid=(),
                check_capacity: bool = False) -> FleetReport:
    """Sweep the whole fleet through the batched engine.

    ``mesh="production"`` shards every workload to per-device shapes
    under the 16x16 production topology (pass None for global shapes,
    or any Mesh/MeshSpec).  N:M options apply to weight matmuls;
    attention (activation-activation) entries are evaluated dense and
    carry a "dense" verdict, except selected ones (DSA), which the
    ``dsa-skip`` option — added to the default options when the fleet
    has them — may win.  ``crossover=True`` additionally sweeps an
    M grid per unique weight (K, N) to locate the compress-vs-dense
    crossover token count — through the same compiled programs, adding
    zero compiles.  ``ctx_grid`` (cache lengths) sweeps each selected
    entry over it to locate the skip-vs-dense crossover context.
    ``seq_len`` is the decode cache's length.
    """
    import time
    from repro.configs import ARCH_NAMES
    if config_names is None:
        config_names = ARCH_NAMES
    if mesh == "production":
        mesh = production_mesh_spec()
    given = options
    if options is None:
        options = default_options(nm_options)
    if not options or options[0].densities is not None:
        raise ValueError("options[0] must be the dense baseline")

    t0 = time.perf_counter()
    sweep_span = obs.span(
        "fleet.sweep", configs=len(tuple(config_names)),
        phases=list(phases), reduced=reduced)
    with sweep_span as sw, compile_stats.track() as st:
        with obs.span("fleet.extract", configs=len(tuple(config_names))):
            nets: list[NetworkWorkloads] = extract_fleet(
                config_names, reduced=reduced, phases=phases, mesh=mesh,
                seq_len=seq_len, batch=batch)
        entries = [(net, e) for net in nets for e in net.matmuls
                   if include_attention or e.param_instances > 0]
        flat = [e for _, e in entries]
        if given is None and any(e.select is not None for e in flat):
            options = [*options, dsa_option()]
        bound = compile_bound(options, flat, check_capacity=check_capacity,
                              crossover=crossover)

        per_option: dict[str, tuple[list[dict], list[int]]] = {}
        for opt in options:
            pool_ix = [i for i, e in enumerate(flat) if opt.applies_to(e)]
            unique, index = dedupe_shapes([flat[i] for i in pool_ix])
            compile_stats.record_dedup_evals(len(pool_ix) - len(unique))
            with obs.span("fleet.option", option=opt.name,
                          phase="evaluate", shapes=len(unique),
                          dedup=len(pool_ix) - len(unique),
                          selected=sum(u[3] is not None for u in unique)):
                res = _evaluate_shapes(opt, unique,
                                       check_capacity=check_capacity)
            fanned = {gi: res[index[j]]
                      for j, gi in enumerate(pool_ix)}
            per_option[opt.name] = fanned

        rows = []
        for i, (net, e) in enumerate(entries):
            dense = per_option["dense"][i]
            best = ("dense", dense["cycles"], 1.0)
            opt_results = {}
            for opt in options:
                r = per_option[opt.name].get(i)
                if r is None:
                    continue
                opt_results[opt.name] = r
                if (opt.name != "dense"
                        and r["cycles"] * WIN_MARGIN < best[1]):
                    best = (opt.name, r["cycles"],
                            r["energy_pj"] / dense["energy_pj"])
            rows.append(LayerVerdict(
                config=net.config, phase=net.phase, layer=e.name,
                M=e.M, K=e.K, N=e.N, count=e.count,
                dense_cycles=dense["cycles"],
                dense_energy_pj=dense["energy_pj"],
                best_option=best[0], best_cycles=best[1],
                best_energy_ratio=best[2], options=opt_results,
                densities=e.densities))

        cross: dict = {}
        if crossover:
            kns = sorted({(e.K, e.N) for e in flat
                          if e.param_instances > 0})
            grid = list(crossover_grid)
            shapes = [(m, K, N, None) for K, N in kns for m in grid]
            weight_options = [o for o in options if not o.selected_only]
            with obs.span("fleet.crossover", kn_shapes=len(kns),
                          grid=len(grid)):
                by_opt = {opt.name: _evaluate_shapes(
                    opt, shapes, check_capacity=check_capacity)
                    for opt in weight_options}
            for ki, (K, N) in enumerate(kns):
                here: dict = {}
                for opt in weight_options:
                    if opt.name == "dense":
                        continue
                    last_win = None
                    for mi, m in enumerate(grid):
                        d = by_opt["dense"][ki * len(grid) + mi]
                        r = by_opt[opt.name][ki * len(grid) + mi]
                        if r["cycles"] * WIN_MARGIN < d["cycles"]:
                            last_win = m
                    here[opt.name] = last_win
                cross[f"{K}x{N}"] = here

        ctx_cross = (_ctx_crossover(options, flat, ctx_grid,
                                    check_capacity=check_capacity)
                     if ctx_grid else {})

        sw.set(entries=len(flat),
               unique_shapes=len(dedupe_shapes(flat)[0]),
               compile_bound=bound)

    total_computes = sum(e.M * e.K * e.N * e.count for e in flat)
    return FleetReport(
        rows=rows, option_names=tuple(o.name for o in options),
        crossover=cross, ctx_crossover=ctx_cross, stats=st.as_dict(),
        compile_bound=bound,
        unique_shapes=len(dedupe_shapes(flat)[0]),
        total_entries=len(flat),
        total_flops=float(sum(e.flops for e in flat)),
        total_dense_computes=float(total_computes),
        wall_seconds=time.perf_counter() - t0)


def _ctx_crossover(options, entries, ctx_grid, *,
                   check_capacity: bool = False) -> dict:
    """Per selected entry, each option that applies to it (but dense):
    the smallest cache length L on ``ctx_grid`` at which it beats dense
    by ``WIN_MARGIN``, the selection's k fixed (capped at L), or None.
    Runs in the ``fleet.ctx_crossover`` span (``rows``: rows evaluated,
    over every option)."""
    grid = sorted(ctx_grid)
    sel: dict = {}
    for e in entries:
        if e.select is None:
            continue
        rank = e.select[1]
        dims = [("L" if r == rank else str(v))
                for r, v in zip("mkn", e.shape)]
        sel.setdefault(f"{e.name}:{'x'.join(dims)}", e)
    if not sel:
        return {}
    rows = []
    for e in sel.values():
        for L in grid:
            g = dataclasses.replace(e, **{e.select[1].upper(): L})
            rows.append((*g.shape, g.densities))
    skip = [o for o in options[1:] if any(o.applies_to(e)
                                          for e in sel.values())]
    with obs.span("fleet.ctx_crossover", entries=len(sel), grid=len(grid),
                  rows=len(rows) * (1 + len(skip))):
        dense = _evaluate_shapes(options[0], rows,
                                 check_capacity=check_capacity)
        by_opt = {o.name: _evaluate_shapes(o, rows,
                                           check_capacity=check_capacity)
                  for o in skip}
    out: dict = {}
    for ei, (key, e) in enumerate(sel.items()):
        here: dict = {}
        for o in skip:
            if not o.applies_to(e):
                continue
            first = None
            for li, L in enumerate(grid):
                i = ei * len(grid) + li
                if by_opt[o.name][i]["cycles"] * WIN_MARGIN \
                        < dense[i]["cycles"]:
                    first = L
                    break
            here[o.name] = first
        out[key] = here
    return out
