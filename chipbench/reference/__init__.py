"""The plain reference of the benchmark: Sparseloop's three-step
analytical model (dataflow -> sparse -> micro-architecture), one mapping
at a time, in Python floats.

It is a copy of the program's scalar model, kept here so that no change
to the program can change what the benchmark compares against; it
imports nothing of the program.  :func:`evaluate` is its one entry.
``num.precision`` runs it in a lower precision, for the control.
"""
from __future__ import annotations

from . import num
from .dataflow import analyze_dataflow
from .density import make_density_model
from .mapping import Loop, LoopNest, nest
from .microarch import evaluate_microarch
from .presets import Design, design
from .sparse import analyze_sparse
from .workload import Workload, matmul

__all__ = ["Design", "Loop", "LoopNest", "Workload", "design", "evaluate",
           "matmul", "nest", "num", "tpu_mapping"]


def evaluate(des: Design, workload: Workload, loops: LoopNest,
             check_capacity: bool = True) -> dict:
    """cycles, energy_pj, edp and valid of one mapping."""
    if loops.num_levels != des.arch.num_levels:
        raise ValueError(
            f"mapping has {loops.num_levels} levels, architecture "
            f"{des.arch.name} has {des.arch.num_levels}")
    models = {t.name: make_density_model(workload.density_spec(t.name),
                                         t.size(workload.rank_bounds))
              for t in workload.tensors}
    dense = analyze_dataflow(workload, loops)
    sparse = analyze_sparse(dense, des.safs, des.level_names, models)
    res = evaluate_microarch(des.arch, sparse, check_capacity=check_capacity)
    return {"cycles": res.cycles, "energy_pj": res.energy_pj,
            "edp": res.edp, "valid": res.valid}


def _div_floor(x: int, target: int) -> int:
    """Largest divisor of x that is <= target."""
    best = 1
    for d in range(1, int(num.isqrt(x)) + 1):
        if x % d == 0:
            if d <= target:
                best = max(best, d)
            if x // d <= target:
                best = max(best, x // d)
    return best


def tpu_mapping(M: int, K: int, N: int, *, bm: int = 2048, bn: int = 2048,
                bk: int = 1024, macs: int = 104448) -> LoopNest:
    """The fleet sweep's canonical HBM->VMEM->REG/MXU mapping: a (bm x bn)
    output tile spread spatially over the MXU, k streamed temporally, and
    a k-spatial factor for the systolic depth.  Unit-bound loops are
    dropped, as the program's bucket program treats them as absent."""
    bm = _div_floor(M, bm)
    bn = _div_floor(N, bn)
    bk = _div_floor(K, bk)
    ksp = _div_floor(bk, max(1, macs // max(1, bm * bn)))
    bk2 = bk // ksp
    mo, no, ko = M // bm, N // bn, K // bk
    specs = (("m", mo, 2), ("n", no, 2), ("k", ko, 2),
             ("k", bk2, 1), ("m", bm, 1, "spatial"), ("n", bn, 1, "spatial"),
             ("k", ksp, 0, "spatial"))
    return nest(3, *(s for s in specs if s[1] > 1))
