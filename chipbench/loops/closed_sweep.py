"""Closed loop of fleet sweeps: one caller, back-to-back ``fleet_sweep``
calls over the configuration's models, phases and options.

Mix keys: ``crossover`` (sweep the M grid of every weight shape too),
``sample_rows`` and ``sample_crossover`` (how many answers the check
draws from the seed), and ``limits``.  The configuration gives the
models (each by its name and widths), ``phases``, ``seq_len``, ``batch``,
``mesh``, whether the models are the reduced presets (``reduced_models``),
the M grid (``crossover_grid``) and, per option, the design and weight
densities the reference evaluates.

Answers checked once the window has closed:

* every sweep's extraction, exactly: its rows (model, phase, layer,
  per-device M, K, N and count), its entry and unique-shape counts, its
  dense MACs and the weight shapes of its crossover grid, against the
  reference's extraction from the widths in the configuration file
  (``chipbench/reference/fleet.py``);
* rows drawn from the seed across every sweep of the window, each under
  every option it carries, against the reference's evaluation of the
  sweep's own mapping;
* the crossover of weight shapes drawn from the seed, recomputed from
  the reference's cycles over the whole M grid (exact).
"""
from __future__ import annotations

import time
import types

from chipbench import common, oracle

#: an option must beat dense by this factor to win (the sweep's rule)
WIN_MARGIN = 1.002


def _options(cfg) -> list[tuple[int, int]]:
    return [tuple(o["nm"]) for o in cfg["options"].values() if "nm" in o]


def setup(ctx):
    from repro import obs
    from repro.fleet.sweep import fleet_sweep
    cfg = ctx.cfg
    models = ctx.mix.get("models") or cfg["models"]
    kw = dict(reduced=bool(cfg.get("reduced_models", False)),
              phases=tuple(cfg["phases"]), seq_len=int(cfg["seq_len"]),
              nm_options=tuple(_options(cfg)),
              crossover=bool(ctx.mix["crossover"]),
              crossover_grid=tuple(cfg["crossover_grid"]))
    st = types.SimpleNamespace(ctx=ctx, models=list(models), kw=kw,
                               sweep=fleet_sweep, obs=obs)
    if ctx.tracing:
        obs.enable()
    # one sweep compiles every program the window runs
    with common.annotate("bench.sweep"):
        fleet_sweep(st.models, **kw)
    return st


def window(st, seconds: float) -> dict:
    sweeps = []
    tracer = st.obs.tracer()
    t0 = time.perf_counter()
    while True:
        t_s = time.perf_counter()
        with common.annotate("bench.sweep"):
            rep = st.sweep(st.models, **st.kw)
        t_e = time.perf_counter()
        sweeps.append({"wall_s": t_e - t_s,
                       "eval_s": rep.eval_seconds, "report": rep})
        st.ctx.tick()
        if t_e - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    records = {"sweeps": sweeps, "window_s": window_s,
               "attempted": len(sweeps), "failed": 0,
               "completed": len(sweeps)}
    if tracer is not None:
        # the sweeps' own spans, ours only (set-up's first sweep left out)
        epoch = tracer.epoch
        records["spans"] = [s for s in tracer.spans
                            if s.t_start + epoch >= t0]
    return records


def end_to_end(records) -> dict:
    return {"sweep_s": records["window_s"] / max(1, records["completed"])}


def modelled(st, records) -> list[str]:
    rep = records["sweeps"][-1]["report"]
    counts: dict = {}
    for r in rep.rows:
        key = f"{r.phase}:{r.best_option}"
        counts[key] = counts.get(key, 0) + 1
    return [f"[modelled] verdicts {sorted(counts.items())}",
            f"[modelled] entries {rep.total_entries} unique_shapes "
            f"{rep.unique_shapes} dense_computes {rep.total_dense_computes!r}",
            f"[modelled] crossover {sorted(rep.crossover.items())}"]


def _workload(M, K, N, densities):
    return lambda: oracle.ref_workload({"M": M, "K": K, "N": N},
                                       densities)


def _reference(cfg, option: str, M: int, K: int, N: int,
               control: bool) -> dict:
    from chipbench import reference
    opt = cfg["options"][option]
    return oracle.evaluate(opt["design"],
                           _workload(M, K, N, opt.get("densities")),
                           reference.tpu_mapping(M, K, N), control)


def _extraction_wrong(st, rep) -> int:
    """Differences between one sweep's extraction and the reference's:
    rows missing or extra, and each total that disagrees."""
    from collections import Counter

    from chipbench.reference import fleet
    ref = fleet.fleet(st.ctx.cfg, st.models)
    want = Counter((model, phase, name, M, K, N, count)
                   for (model, phase), entries in ref.items()
                   for name, M, K, N, count, _ in entries)
    got = Counter((r.config, r.phase, r.layer, r.M, r.K, r.N, r.count)
                  for r in rep.rows)
    flat = list(want.elements())
    wrong = sum(((got - want) + (want - got)).values())
    wrong += rep.total_entries != len(flat)
    wrong += rep.unique_shapes != len({e[3:6] for e in flat})
    wrong += rep.total_dense_computes != float(
        sum(M * K * N * count for *_, M, K, N, count in flat))
    if st.kw["crossover"]:
        weights = {f"{K}x{N}" for entries in ref.values()
                   for _, _, K, N, _, tp in entries if tp != "attn"}
        wrong += len(set(rep.crossover) ^ weights)
    return wrong


def check(st, records, ctx) -> list:
    cfg, mix = ctx.cfg, ctx.mix
    extraction = sum(_extraction_wrong(st, s["report"])
                     for s in records["sweeps"])
    rng = common.rng(ctx.seed, 3)
    rows = [(si, ri) for si, s in enumerate(records["sweeps"])
            for ri in range(len(s["report"].rows))]
    picks = rng.choice(len(rows), size=min(len(rows),
                                           int(mix["sample_rows"])),
                       replace=False)
    rel = []
    for p in picks:
        si, ri = rows[int(p)]
        row = records["sweeps"][si]["report"].rows[ri]
        for name, got in row.options.items():
            ref = _reference(cfg, name, row.M, row.K, row.N, False)
            if ctx.control:
                got = _reference(cfg, name, row.M, row.K, row.N, True)
            rel += [oracle.rel_dev(got[k], ref[k]) for k in oracle.STATS]
    checks = [oracle.Check("extraction_wrong", extraction, 0),
              oracle.Check("row_rel", oracle.worst(rel),
                           mix["limits"]["row_rel"])]
    if mix["crossover"]:
        cross = records["sweeps"][-1]["report"].crossover
        keys = sorted(cross)
        picks = rng.choice(len(keys), size=min(len(keys),
                                               int(mix["sample_crossover"])),
                           replace=False)
        wrong = 0
        for p in picks:
            K, N = map(int, keys[int(p)].split("x"))
            for name, last_win in cross[keys[int(p)]].items():
                want = None
                for m in cfg["crossover_grid"]:
                    d = _reference(cfg, "dense", m, K, N, ctx.control)
                    r = _reference(cfg, name, m, K, N, ctx.control)
                    if r["cycles"] * WIN_MARGIN < d["cycles"]:
                        want = m
                wrong += want != last_win
        checks.append(oracle.Check("crossover_wrong", wrong, 0))
    return checks
