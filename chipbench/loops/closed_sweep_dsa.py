"""Closed loop of DeepSeek Sparse Attention decode sweeps: one caller,
back-to-back ``fleet_sweep`` calls over one DSA model's decode step,
its selected score and value products swept over a context grid too.

Mix keys: ``sample_rows`` (how many rows the check draws from the seed),
``limits``, and ``rehearse`` (for ``--rehearse``: the program's reduced
preset, ``reduced_models``, which reports itself as ``model_name``, with
its ``widths`` laid over the configuration's for the reference, and
smaller ``seq_len``, ``batch`` and ``ctx_grid``).  The configuration
gives the model (``model``, and its widths under the keys of its
``config.json``), ``phases``, ``seq_len`` (the cache), ``batch``,
``mesh``, ``ctx_grid`` and, per option, the design the reference
evaluates.  The weight crossover is
off (the fleet6 cell sweeps it).

Answers checked once the window has closed:

* every sweep's extraction, exactly, against the reference's
  (``chipbench/reference/dsa.py``): rows (model, phase, layer,
  per-device M, K, N, count), entry and unique-row counts, dense MACs;
* that every selected entry was evaluated with its selection
  (``selected_missing``): its row states the reference's selection and
  carries the ``dsa-skip`` option, and no other row carries it;
* rows drawn from the seed across every sweep, each under every option
  it carries and with its own densities, against the reference on the
  sweep's own mapping;
* the context crossover of every sweep, recomputed from the reference's
  cycles over the whole grid (exact).
"""
from __future__ import annotations

import types

from chipbench import common, oracle
from chipbench.loops import closed_sweep

#: an option must beat dense by this factor to win (the sweep's rule)
WIN_MARGIN = 1.002

window = closed_sweep.window
end_to_end = closed_sweep.end_to_end


def _widths(ctx) -> dict:
    return {**ctx.cfg, **ctx.mix.get("widths", {})}


def setup(ctx):
    from repro import obs
    from repro.fleet.sweep import fleet_sweep
    cfg, mix = ctx.cfg, ctx.mix
    phases = tuple(cfg["phases"])
    kw = dict(reduced=bool(mix.get("reduced_models", False)),
              phases=phases, seq_len=int(mix.get("seq_len", cfg["seq_len"])),
              batch=int(mix.get("batch", cfg["batch"]["decode"])),
              nm_options=tuple(tuple(o["nm"]) for o in
                               cfg["options"].values() if "nm" in o),
              crossover=False,
              ctx_grid=tuple(mix.get("ctx_grid", cfg["ctx_grid"])))
    st = types.SimpleNamespace(ctx=ctx, models=[cfg["model"]], kw=kw,
                               sweep=fleet_sweep, obs=obs)
    if ctx.tracing:
        obs.enable()
    # one sweep compiles every program the window runs
    with common.annotate("bench.sweep"):
        fleet_sweep(st.models, **kw)
    return st


def modelled(st, records) -> list[str]:
    rep = records["sweeps"][-1]["report"]
    return closed_sweep.modelled(st, records) + [
        f"[modelled] ctx_crossover {sorted(rep.ctx_crossover.items())}"]


def _reference_entries(st) -> list:
    from chipbench.reference import dsa
    kw = st.kw
    return dsa.per_device(dsa.decode(_widths(st.ctx), kw["seq_len"],
                                     kw["batch"]), st.ctx.cfg["mesh"])


def _reference(cfg, option: str, M, K, N, dens, control: bool) -> dict:
    import numpy as np

    from chipbench import reference
    from chipbench.reference import dsa
    spec = cfg["options"][option]
    nm = spec.get("densities")
    if nm:
        dens = {**(dens or {}), **common.densities(nm)}
    with reference.num.precision(np.float32 if control else float):
        des = dsa.design(spec["design"], dens)
        return dsa.evaluate(des, M, K, N, dens,
                            reference.tpu_mapping(M, K, N))


def _extraction_wrong(st, rep, ref) -> int:
    from collections import Counter

    from chipbench.reference import dsa
    # the reduced preset reports its own name
    model = st.ctx.mix.get("model_name", st.models[0])
    want = Counter((model, "decode", name, M, K, N, count)
                   for name, M, K, N, count, _, _ in ref)
    got = Counter((r.config, r.phase, r.layer, r.M, r.K, r.N, r.count)
                  for r in rep.rows)
    wrong = sum(((got - want) + (want - got)).values())
    wrong += rep.total_entries != len(ref)
    rows = {(M, K, N, repr(dsa.densities(M, K, N, sel)))
            for _, M, K, N, _, _, sel in ref}
    wrong += rep.unique_shapes != len(rows)
    wrong += rep.total_dense_computes != float(
        sum(M * K * N * count for _, M, K, N, count, _, _ in ref))
    return wrong


def _selected_missing(rep, ref) -> int:
    from chipbench.reference import dsa
    want = {(name, M, K, N): dsa.densities(M, K, N, sel)
            for name, M, K, N, _, _, sel in ref}
    missing = 0
    for r in rep.rows:
        dens = want.get((r.layer, r.M, r.K, r.N))
        if dens is None:
            missing += "dsa-skip" in r.options
        else:
            missing += r.densities != dens or "dsa-skip" not in r.options
    return missing


def _ctx_crossover(cfg, ref, grid, control: bool) -> dict:
    """The reference's crossover: per selected entry (the selected rank
    written "L"), the smallest cache length on the grid at which
    ``dsa-skip`` beats dense."""
    from chipbench.reference import dsa
    out: dict = {}
    for name, M, K, N, _, _, sel in ref:
        if sel is None:
            continue
        rank = sel[1]
        dims = [("L" if r == rank else str(v))
                for r, v in zip("mkn", (M, K, N))]
        first = None
        for L in sorted(grid):
            shape = {"m": M, "k": K, "n": N, rank: L}
            M_, K_, N_ = shape["m"], shape["k"], shape["n"]
            dens = dsa.densities(M_, K_, N_, sel)
            d = _reference(cfg, "dense", M_, K_, N_, dens, control)
            r = _reference(cfg, "dsa-skip", M_, K_, N_, dens, control)
            if r["cycles"] * WIN_MARGIN < d["cycles"]:
                first = L
                break
        out[f"{name}:{'x'.join(dims)}"] = {"dsa-skip": first}
    return out


def check(st, records, ctx) -> list:
    cfg, mix = ctx.cfg, ctx.mix
    ref = _reference_entries(st)
    sweeps = [s["report"] for s in records["sweeps"]]
    extraction = sum(_extraction_wrong(st, rep, ref) for rep in sweeps)
    missing = sum(_selected_missing(rep, ref) for rep in sweeps)
    rng = common.rng(ctx.seed, 3)
    rows = [(si, ri) for si, rep in enumerate(sweeps)
            for ri in range(len(rep.rows))]
    picks = rng.choice(len(rows), size=min(len(rows),
                                           int(mix["sample_rows"])),
                       replace=False)
    rel = []
    for p in picks:
        si, ri = rows[int(p)]
        row = sweeps[si].rows[ri]
        for name, got in row.options.items():
            want = _reference(cfg, name, row.M, row.K, row.N,
                              row.densities, False)
            if ctx.control:
                got = _reference(cfg, name, row.M, row.K, row.N,
                                 row.densities, True)
            rel += [oracle.rel_dev(got[k], want[k]) for k in oracle.STATS]
    cross = _ctx_crossover(cfg, ref, st.kw["ctx_grid"], ctx.control)
    cross_wrong = 0
    for rep in sweeps:
        for key in set(cross) | set(rep.ctx_crossover):
            cross_wrong += cross.get(key) != rep.ctx_crossover.get(key)
    return [oracle.Check("extraction_wrong", extraction, 0),
            oracle.Check("row_rel", oracle.worst(rel),
                         mix["limits"]["row_rel"]),
            oracle.Check("ctx_crossover_wrong", cross_wrong, 0),
            oracle.Check("selected_missing", missing, 0)]
