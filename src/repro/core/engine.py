"""Sparseloop engine: orchestrates the three decoupled modeling steps
(Fig. 5): dataflow modeling -> sparse modeling -> micro-architectural
modeling.

The decoupling is the paper's central modeling insight (Sec. 4.2):
dataflow is evaluated independent of SAFs, SAFs independent of
micro-architecture — which lets one infrastructure model both dense and
sparse designs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Sequence

import numpy as np

from .. import obs
from .arch import Architecture
from .dataflow import DenseTraffic, analyze_dataflow
from .density import DensityModel, make_density_model
from .mapping import LoopNest
from .microarch import EvalResult, evaluate_microarch
from .sparse import SparseTraffic, analyze_sparse
from .taxonomy import SAFSpec
from .workload import Workload


@dataclasses.dataclass(frozen=True)
class Design:
    """A point in the design space: Architecture x SAFs (dataflow comes in
    as the mapping at evaluation time — Sec. 3.2: dataflow is orthogonal)."""

    arch: Architecture
    safs: SAFSpec
    name: str = ""

    @property
    def level_names(self) -> list[str]:
        """Innermost-first storage level names (mapping level indices)."""
        return [self.arch.level(s).name for s in range(self.arch.num_levels)]


@dataclasses.dataclass
class Evaluation:
    """Bundled result of one (design, workload, mapping) evaluation."""

    result: EvalResult
    dense: DenseTraffic
    sparse: SparseTraffic
    wall_seconds: float

    @property
    def cycles(self) -> float:
        return self.result.cycles

    @property
    def energy_pj(self) -> float:
        return self.result.energy_pj

    @property
    def edp(self) -> float:
        return self.result.edp


def _scatter(out: dict, n: int, idxs, res: dict) -> None:
    """Write a group's result columns into ``out``'s (n, ...) columns
    at the input positions ``idxs``."""
    for k, v in res.items():
        v = np.asarray(v)
        if k not in out:
            # some columns carry trailing axes (e.g. per-level occupancy
            # is (C, S))
            out[k] = np.zeros((n,) + v.shape[1:],
                              dtype=bool if k == "valid" else np.float64)
        out[k][idxs] = v


class Sparseloop:
    """The analytical model.  Fast because it is statistical: it never
    iterates the computation space (Sec. 6.2).

    ``evaluate`` is the scalar reference oracle (one mapping at a time);
    ``evaluate_batch`` lowers a whole candidate population onto the
    vectorized JAX engine (core.batched) — same math, one jitted
    computation per loop-structure template.
    """

    def __init__(self, design: Design):
        self.design = design

    def evaluate(self, workload: Workload, nest: LoopNest,
                 models: dict[str, DensityModel] | None = None,
                 check_capacity: bool = True) -> Evaluation:
        t0 = time.perf_counter()
        if nest.num_levels != self.design.arch.num_levels:
            raise ValueError(
                f"mapping has {nest.num_levels} levels, architecture "
                f"{self.design.arch.name} has {self.design.arch.num_levels}")
        if models is None:
            models = {
                t.name: make_density_model(
                    workload.density_spec(t.name),
                    t.size(workload.rank_bounds))
                for t in workload.tensors
            }
        dense = analyze_dataflow(workload, nest)                 # step 1
        sparse = analyze_sparse(dense, self.design.safs,         # step 2
                                self.design.level_names, models)
        result = evaluate_microarch(self.design.arch, sparse,    # step 3
                                    check_capacity=check_capacity)
        return Evaluation(result=result, dense=dense, sparse=sparse,
                          wall_seconds=time.perf_counter() - t0)

    # ------------------------------------------------------------------
    def batched_model(self, workload: Workload, template,
                      check_capacity: bool = True, caps=None):
        """Compiled batched evaluator for one loop-structure template
        (content-cached — facades for workloads with equal *structure*
        share the underlying compiled program; ``caps`` forces common
        density capacities across a mixed-density sweep)."""
        from .batched import get_batched_model
        return get_batched_model(self.design, workload, template,
                                 check_capacity=check_capacity, caps=caps)

    def bucketed_model(self, workload: Workload, bucket,
                       check_capacity: bool = True, caps=None):
        """Compiled bucketed evaluator for one padded template family
        (content-cached — facades for workloads with equal *structure*
        share the underlying compiled program; ``caps`` forces common
        density capacities across a mixed-density sweep)."""
        from .batched import get_bucketed_model
        return get_bucketed_model(self.design, workload, bucket,
                                  check_capacity=check_capacity, caps=caps)

    def evaluate_batch(self, workload: Workload,
                       nests: Sequence[LoopNest] | Iterable[LoopNest],
                       check_capacity: bool = True,
                       bucketed: bool = True,
                       caps=None) -> dict[str, np.ndarray]:
        """Evaluate a population of mappings in one (or a few) jitted JAX
        computations.

        Candidates are grouped by *bucket* (padded template family,
        ``core.batched.TemplateBucket``): each bucket's candidates —
        whatever their loop order — are lowered onto one compiled
        program, with per-candidate rank ids carrying the permutation as
        data.  A mixed-permutation population therefore costs a handful
        of compiles (one per bucket) instead of one per loop structure;
        pass ``bucketed=False`` for the legacy one-compile-per-exact-
        template grouping.  Workload parameters (rank bounds, density
        models — actual-data included, via its tile-occupancy histogram)
        are traced inputs, so layers of equal structure reuse compiled
        programs across calls; ``caps`` (see ``batched.common_caps``)
        aligns the static density capacities of a mixed-density sweep.
        Returns per-candidate arrays aligned with the input order:
        cycles, energy_pj, edp, valid, compute_actual/gated/skipped.
        """
        return self._grouped_eval(workload, nests, check_capacity,
                                  bucketed, caps, [None])[0]

    def _grouped_eval(self, workload: Workload, nests, check_capacity,
                      bucketed, caps, arch_params_list
                      ) -> list[dict[str, np.ndarray]]:
        """Shared grouped dispatch of ``evaluate_batch`` /
        ``evaluate_designs``: lower the population once per group, then
        bind each entry of ``arch_params_list`` (None = the engine's
        own design) to the group's compiled program.  Returns one
        result dict per entry, each aligned with the input order.

        The ``engine.batch`` span covers the whole call; its self time
        (less the ``engine.compile`` / ``engine.eval`` spans inside) is
        the host's lowering and scatter."""
        from .batched import group_by_bucket, group_by_template, lower_nests
        nests = list(nests)
        outs: list[dict[str, np.ndarray]] = [{}
                                             for _ in arch_params_list]

        with obs.span("engine.batch", candidates=len(nests)) as sp:
            if not bucketed:
                groups = group_by_template(nests)
                sp.set(groups=len(groups))
                for template, idxs in groups.items():
                    model = self.batched_model(workload, template,
                                               check_capacity, caps=caps)
                    bounds = np.stack([template.bounds_of(nests[i])
                                       for i in idxs])
                    for out, ap in zip(outs, arch_params_list):
                        _scatter(out, len(nests), idxs,
                                 model.evaluate(bounds, arch_params=ap))
                return outs

            ranks = tuple(workload.rank_bounds)
            groups = group_by_bucket(nests, ranks)
            sp.set(groups=len(groups))
            for bucket, idxs in groups.items():
                model = self.bucketed_model(workload, bucket,
                                            check_capacity, caps=caps)
                bounds, ids, order = lower_nests(bucket, nests, idxs)
                for out, ap in zip(outs, arch_params_list):
                    _scatter(out, len(nests), order,
                             model.evaluate(bounds, ids, arch_params=ap))
            return outs

    def evaluate_rows(self, workloads: Sequence[Workload],
                      nests: Sequence[LoopNest],
                      check_capacity: bool = True
                      ) -> list[dict[str, np.ndarray]]:
        """Evaluate one mapping per workload: row i is ``nests[i]`` on
        ``workloads[i]``, the workloads all of one structure.

        Where :meth:`evaluate_network` makes a call per workload, this
        puts rows of different workloads on one candidate axis: every
        row's params are packed against the rows' ``common_caps`` and
        stacked, the rows grouped by bucket, and each bucket's rows run
        through its program's row variant in blocks of
        ``batched.ROW_BLOCK`` (``BucketedModel.evaluate_rows``), so any
        row count costs one compile per bucket.  Returns one result
        dict per row, in input order.  The ``engine.batch`` span counts
        the ``rows``, the ``padded`` rows that fill the last block of
        each bucket, and the ``blocks`` (program calls)."""
        from .batched import (ROW_BLOCK, common_caps, group_by_bucket,
                              lower_nests, pack_workload_params,
                              stack_workload_params)
        workloads = list(workloads)
        nests = list(nests)
        if len(workloads) != len(nests):
            raise ValueError(f"{len(workloads)} workloads but "
                             f"{len(nests)} nests")
        if not nests:
            return []
        out: dict[str, np.ndarray] = {}
        with obs.span("engine.batch", rows=len(nests)) as sp:
            caps = common_caps(workloads)
            params = [pack_workload_params(wl, caps) for wl in workloads]
            groups = group_by_bucket(nests, tuple(workloads[0].rank_bounds))
            blocks = 0
            for bucket, idxs in groups.items():
                model = self.bucketed_model(workloads[idxs[0]], bucket,
                                            check_capacity, caps=caps)
                bounds, ids, order = lower_nests(bucket, nests, idxs)
                res = model.evaluate_rows(
                    bounds, ids,
                    stack_workload_params([params[i] for i in order]))
                _scatter(out, len(nests), order, res)
                blocks += -(-len(order) // ROW_BLOCK)
            sp.set(groups=len(groups), blocks=blocks,
                   padded=blocks * ROW_BLOCK - len(nests))
        return [{k: v[i] for k, v in out.items()}
                for i in range(len(nests))]

    def evaluate_network(self, workloads: Sequence[Workload],
                         nests_per_workload,
                         check_capacity: bool = True,
                         bucketed: bool = True
                         ) -> list[dict[str, np.ndarray]]:
        """Evaluate one candidate population per network layer through
        *shared* compiled programs.

        The common density capacities of all layers are computed up
        front, so structurally-identical layers — whatever their rank
        bounds or density kinds (uniform / structured / banded /
        actual-data mixed freely) — lower onto the same (arch, bucket)
        program: an N-layer sweep costs O(#buckets) compiles,
        independent of N.  Returns one ``evaluate_batch``-shaped dict
        per layer, aligned with ``workloads``."""
        from .batched import common_caps
        workloads = list(workloads)
        nests_per_workload = list(nests_per_workload)
        if len(workloads) != len(nests_per_workload):
            raise ValueError(
                f"{len(workloads)} workloads but "
                f"{len(nests_per_workload)} nest populations")
        caps = common_caps(workloads)
        return [self.evaluate_batch(wl, nests,
                                    check_capacity=check_capacity,
                                    bucketed=bucketed, caps=caps)
                for wl, nests in zip(workloads, nests_per_workload)]

    def evaluate_designs(self, archs, workload: Workload, nests,
                         check_capacity: bool = True,
                         bucketed: bool = True,
                         caps=None) -> list[dict[str, np.ndarray]]:
        """Cross-product design sweep: evaluate one candidate population
        under every architecture in ``archs`` through *shared* compiled
        programs.

        Architecture scalars (capacities, bandwidths, per-action
        energies, PE counts) are traced ``ArchParams`` inputs of the
        programs, which are keyed by canonical *topology key* (level
        names + SAF placement, ``arch.topology_key``).  ``archs`` mixes
        freely: ``Architecture``s (riding this engine's SAF spec) and
        ``Design``s carrying their OWN SAF specs — entries are grouped
        by topology key and each group binds its params to its group's
        programs, so a heterogeneous sweep compiles O(topology groups x
        buckets) programs, independent of the number of design points.
        The candidate nests are shared across every entry, so level
        COUNTS must match this engine's (heterogeneous level counts
        need per-candidate nests — that lives in the search layer,
        ``TopologyCoSearchEncoding``).  Returns one
        ``evaluate_batch``-shaped dict per arch, aligned with
        ``archs``."""
        from .arch import pack_arch_params, topology_key
        base = self.design
        base_key = topology_key(base.arch, base.safs)
        members: dict[tuple, list[int]] = {}
        reps: dict[tuple, Design] = {}
        params: list = []
        for pos, a in enumerate(archs):
            d = a if isinstance(a, Design) \
                else dataclasses.replace(base, arch=a)
            if d.arch.num_levels != base.arch.num_levels:
                raise ValueError(
                    f"architecture {d.arch.name!r} has topology with "
                    f"{d.arch.num_levels} levels; the shared nest "
                    f"population is lowered for "
                    f"{base.arch.num_levels} — heterogeneous level "
                    f"counts need per-candidate nests "
                    f"(search.TopologyCoSearchEncoding)")
            key = topology_key(d.arch, d.safs)
            members.setdefault(key, []).append(pos)
            reps.setdefault(key, d)
            params.append(pack_arch_params(d.arch))
        outs: list = [None] * len(params)
        for key, idxs in members.items():
            engine = self if key == base_key else Sparseloop(reps[key])
            res = engine._grouped_eval(
                workload, nests, check_capacity, bucketed, caps,
                [params[i] for i in idxs])
            for pos, r in zip(idxs, res):
                outs[pos] = r
        return outs

    # ------------------------------------------------------------------
    def cphc(self, workload: Workload, nest: LoopNest,
             host_hz: float = 3.0e9, **kw) -> float:
        """Computes-simulated-per-host-cycle (the paper's speed metric,
        Sec. 6.2): dense computes modeled / host cycles spent modeling."""
        ev = self.evaluate(workload, nest, **kw)
        host_cycles = ev.wall_seconds * host_hz
        return ev.dense.dense_computes / max(1.0, host_cycles)
