"""Closed loop of mapspace searches whose population is sharded over
every chip of the host: one caller, back-to-back ``run_search`` calls
over the configuration's layers, round-robin, on the host loop with
``mesh=population_mesh(chips)`` (the fused scan ignores ``mesh``).

Mix keys as ``closed_search`` (``strategy``, ``pop_size``,
``generations``, ``layers``, ``limits``; ``fused`` must be false).  The
window, its end-to-end metric, the modelled lines and the check are
``closed_search``'s: each search's device-side best (the host loop
records the best of each generation's sharded evaluation) and its
validated winner against the reference, its generations and
evaluations, and the stalled and invalid shares.  A run on the chip
refuses a host with fewer chips than the cell asks for; a ``--rehearse``
run on one CPU device searches unsharded.
"""
from __future__ import annotations

import types

from chipbench import common
from chipbench.loops import closed_search

window = closed_search.window
end_to_end = closed_search.end_to_end
modelled = closed_search.modelled
check = closed_search.check


def setup(ctx):
    from repro.search import run_search
    from repro.search.runner import population_mesh
    cfg, mix = ctx.cfg, ctx.mix
    if mix["fused"]:
        raise ValueError("the fused scan ignores the mesh: this loop runs "
                         "the host loop (fused false)")
    mesh = population_mesh(ctx.chips)
    if mesh is None and not ctx.rehearse:
        raise RuntimeError(f"no mesh over {ctx.chips} devices")
    names = mix.get("layers") or [lay["name"] for lay in cfg["layers"]]
    layers = [next(lay for lay in cfg["layers"] if lay["name"] == n)
              for n in names]
    st = types.SimpleNamespace(
        ctx=ctx, layers=layers, run_search=run_search,
        design=common.program_design(cfg["design"]),
        workloads=[common.program_workload(lay) for lay in layers],
        cons=common.constraints(
            cfg, int(mix["pop_size"]) * int(mix["generations"])),
        kw=dict(strategy=mix["strategy"], pop_size=int(mix["pop_size"]),
                generations=int(mix["generations"]), mesh=mesh,
                fused=False))
    # one search per layer warms its programs and the oracle walk
    for i in range(len(layers)):
        closed_search._search(st, i, key=common.derive(ctx.seed, 1, i))
    return st
