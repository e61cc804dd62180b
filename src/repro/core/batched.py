"""Batched mapspace evaluation: the three-step Sparseloop model (dataflow
-> sparse -> micro-architecture) vectorized over a *population* of loop
nests with JAX ``vmap`` + ``jit``.

Why this exists (ROADMAP north-star / paper Sec. 6.2): the paper's speed
metric (CPHC) measures one-mapping-at-a-time evaluation.  Because all
three analysis steps are closed-form given the loop *structure*, every
mapping that shares a structure — same (rank, level, spatial) slot
sequence, arbitrary bounds — can be evaluated as one jitted computation:
thousands of mappings per millisecond on CPU, more on accelerators.  This
module generalizes the equations that used to be frozen into
``vmapper.py`` (a single hard-coded two-level spMspM template) to

  * arbitrary storage-level counts,
  * arbitrary rank sets / extended-Einsum projections,
  * arbitrary ``SAFSpec``s: per-(level, tensor) hierarchical formats,
    gating/skipping with leader-follower intersection windows, compression
    metadata — the same math as ``sparse.py``/``formats.py``, traced.

The lowering contract
---------------------
A :class:`NestTemplate` is the loop structure with the bounds stripped.
Bound-1 slots are *allowed* and treated exactly as if the loop were absent
(the scalar mapper never emits unit loops; reuse-prefix and leader-window
boundaries are therefore recomputed per candidate from ``bound > 1``
masks, keeping batched results bit-comparable with the scalar engine's
dropped-unit-loop semantics).

Bucketed lowering (one compile per *family* of templates)
---------------------------------------------------------
Compiling one program per exact template makes free-permutation searches
and multi-layer sweeps pay one multi-second XLA compile per loop order —
hundreds of compiles for a population that evaluates in milliseconds.  A
:class:`TemplateBucket` is the padded superset of a template family: per
storage level it carries the *maximum* slot count over the family, absent
loops ride as unit bounds (inert by the contract above), and — the key
move — the slot->rank assignment is a traced per-candidate gather instead
of a compile-time constant.  Internally the traced program receives a
per-slot rank one-hot matrix: :class:`BatchedModel` passes a constant
(so exact templates behave exactly as before), :class:`BucketedModel`
derives it from a per-candidate ``rank_ids`` array, so every permutation
of every layer of a network evaluates through the *same* compiled
program.  ``bucket_for`` / ``group_by_bucket`` implement the bucketing
policy (pad each level's temporal slot count up to the workload's rank
count, keep the spatial slot shape), bounding the number of compiled
programs for a sweep by the number of distinct (workload, bucket shape)
pairs instead of the number of loop orders.

Workload-as-data (one compile per *architecture x bucket shape*)
----------------------------------------------------------------
Bucketing makes the loop order per-candidate data; this layer makes the
*workload* per-call data.  A :class:`WorkloadParams` packs everything a
layer contributes to the math — the rank bounds vector plus, per tensor,
a density-model kind id, a fixed-shape parameter vector and a
tile-occupancy histogram (``density.TracedDensityStats``) — and the
traced program takes it as a (non-vmapped) traced input.  Compiled
programs are therefore cached by *workload structure* (rank names,
tensor projections, output — :func:`workload_structure`) and static
:class:`~.density.DensityCaps`, never by bounds or density values: every
layer of a network sweep, mixed density kinds included, evaluates
through the same compiled program, making an N-layer sweep O(buckets)
compiles instead of O(layers x buckets).

Architecture-as-data (one compile per *topology x bucket shape*)
----------------------------------------------------------------
The symmetric move for design sweeps: every per-level architecture
scalar — capacity, bandwidth, read/write/gated/metadata energies, MAC
energy, PE count — packs into a fixed-shape traced
:class:`~.arch.ArchParams` (``arch.pack_arch_params``) instead of baking
into the trace.  Programs are keyed by arch *topology*
(:func:`~.arch.arch_structure`: level names + compute name) plus the SAF
structure, and the params ride as a PER-CANDIDATE (vmapped) input:
``evaluate(..., arch_params=)`` binds one design to the whole population
(the facade's own arch by default) or — with a batched params object —
one design point per candidate, which is what lets a mixed-design
(design, mapping) co-search population evaluate through ONE compiled
program.  A design sweep therefore costs O(buckets) compiles,
independent of the number of design points
(``Sparseloop.evaluate_designs``); the sharded path replicates the
workload params across devices and shards the arch rows with their
candidates.

``BatchedModel.evaluate`` matches scalar ``Sparseloop.evaluate`` to
float64 round-off (tests/test_batched.py pins <=1e-6 relative, and
tests/test_bucketed.py pins the padded-bucket path against both); the
scalar engine remains the per-candidate reference oracle.

Every Table-4 density model now has a traced form — the ``actual``-data
model lowers to a per-tensor tile-occupancy histogram gather — so no
workload is scalar-only anymore; :class:`BatchedUnsupported` survives
only for unknown density specs.

When a candidate axis is large and several devices are visible,
``evaluate(..., mesh=...)`` shards the population across the mesh with
``jax.shard_map``: each device vmaps its slice of the population, so
mapspace sweeps scale linearly with device count.

Every traced-program construction and every first-evaluation-at-a-shape
(the moments XLA actually compiles) is counted by
:mod:`repro.core.compile_stats`, so sweeps can assert their compile
budget ("this sweep compiled N programs") — the CI compile-gate rides on
it.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from .. import obs
from . import compile_stats
from .arch import (COMPUTE_FIELDS, STORAGE_FIELDS, ArchParams,
                   Architecture, arch_structure, pack_arch_params,
                   topology_key)
from .density import (ACTUAL_ID, BatchedDensityUnsupported, DensityCaps,
                      DensityModel, HyperBatch, TracedDensityStats,
                      caps_for_models, make_density_model)
from .mapping import Loop, LoopNest
from .taxonomy import RankFormat, SAFSpec, SAFKind
from .workload import TensorSpec, Workload

WORD_BITS = 16.0  # metadata accounting word width (matches sparse.py)


class BatchedUnsupported(NotImplementedError):
    """The (design, workload) pair has no batched path; use the scalar
    engine instead."""


# ----------------------------------------------------------------------
# Workload-as-data: the traced inputs of a compiled program
# ----------------------------------------------------------------------
def workload_structure(workload: Workload) -> tuple:
    """The *static* part of a workload — ordered rank names, tensor
    projections and the output tensor.  Everything else (rank bound
    values, density parameters) is traced :class:`WorkloadParams` data,
    so two layers with equal structure share compiled programs."""
    return (tuple(workload.rank_bounds), workload.tensors,
            workload.output)


@dataclasses.dataclass(frozen=True)
class WorkloadParams:
    """Traced workload inputs of one compiled program.

    ``rank_bounds`` is the (R,) bound vector in ``workload.ranks``
    order; per tensor (in ``workload.tensors`` order) ``model_ids``
    holds the density-model kind, ``density_params`` the fixed-shape
    parameter rows and ``hist`` the ``(3, caps.hist)`` tile-occupancy
    histograms (zero-width when no actual-data tensor exists).  ``caps``
    is the static padding the arrays were built against — it must match
    the program's caps (programs are cached per (arch, structure,
    bucket, caps)), and ``structure`` records which workload structure
    the arrays were packed for so binding them to the wrong program is
    a loud error.

    The histogram block is dense — one ``(3, caps.hist)`` row per
    tensor, zero for non-actual ones — because the density *kind* is
    traced data: any tensor may be actual-data in some layer of the
    sweep, so every tensor needs a row for the program to stay
    layer-agnostic.  The device copy is made once per params object
    (:meth:`device_leaves`)."""

    rank_bounds: np.ndarray
    model_ids: np.ndarray
    density_params: np.ndarray
    hist: np.ndarray
    caps: DensityCaps
    structure: tuple = ()

    def leaves(self) -> tuple:
        """The pytree handed to the jitted program (caps are static)."""
        return (self.rank_bounds, self.model_ids, self.density_params,
                self.hist)

    def device_leaves(self) -> tuple:
        """``leaves()`` as (cached) device arrays — the histogram block
        can be megabytes and the params are immutable, so the
        host-to-device transfer happens once, not per evaluation."""
        cached = getattr(self, "_device_leaves", None)
        if cached is None:
            with enable_x64():      # keep float64 whatever the caller
                cached = tuple(jnp.asarray(x) for x in self.leaves())
            object.__setattr__(self, "_device_leaves", cached)
        return cached


def _density_models(workload: Workload) -> list[DensityModel]:
    return [make_density_model(workload.density_spec(t.name),
                               t.size(workload.rank_bounds))
            for t in workload.tensors]


def pack_workload_params(workload: Workload,
                         caps: DensityCaps | None = None
                         ) -> WorkloadParams:
    """Lower a concrete workload to the traced arrays of its compiled
    program.  ``caps`` pins the static padding — pass
    :func:`common_caps` of all layers of a sweep so every layer packs
    into (and therefore shares) the same program."""
    models = _density_models(workload)
    if caps is None:
        caps = caps_for_models(models)
    else:
        # exact (unrounded) requirement: any caps that fit the real
        # tables/scans are acceptable, pow2 rounding is only a
        # program-sharing heuristic
        need = caps_for_models(models, round_pow2=False)
        if not caps.covers(need):
            raise ValueError(f"caps {caps} do not cover the workload's "
                             f"required {need}")
    for t, m in zip(workload.tensors, models):
        if not m.batched:
            raise BatchedUnsupported(
                f"density model for tensor {t.name!r} "
                f"({type(m).__name__}) has no traced parametric form")
        if m.kind_id == ACTUAL_ID and m.tensor_size == 0:
            raise ValueError(f"actual-data tensor {t.name!r} is empty")
    rank_bounds = np.asarray(list(workload.rank_bounds.values()),
                             np.float64)
    model_ids = np.asarray([m.kind_id for m in models], np.int32)
    density_params = np.stack([np.asarray(m.params(), np.float64)
                               for m in models])
    hist = np.zeros((len(models), 3, caps.hist))
    for i, m in enumerate(models):
        table = m.hist_table()
        hist[i, :, : table.shape[1]] = table
    return WorkloadParams(rank_bounds=rank_bounds, model_ids=model_ids,
                          density_params=density_params, hist=hist,
                          caps=caps, structure=workload_structure(workload))


def common_caps(workloads) -> DensityCaps:
    """The joint :class:`DensityCaps` of several layers — pack every
    layer's :class:`WorkloadParams` against this so they share compiled
    programs."""
    caps = DensityCaps()
    for wl in workloads:
        caps = caps.merge(caps_for_models(_density_models(wl)))
    return caps


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NestTemplate:
    """Loop structure shared by a mapspace slice.

    ``slots`` are (rank, level, spatial) triples, outermost-first — a
    :class:`LoopNest` with the bounds stripped.  All candidates evaluated
    together instantiate this structure with per-slot bounds >= 1.
    """

    slots: tuple[tuple[str, int, bool], ...]
    num_levels: int

    @staticmethod
    def of_nest(nest: LoopNest) -> "NestTemplate":
        return NestTemplate(slots=nest.structure(),
                            num_levels=nest.num_levels)

    @property
    def num_slots(self) -> int:
        return len(self.slots)

    def bounds_of(self, nest: LoopNest) -> np.ndarray:
        """Per-slot bounds of a nest with this structure."""
        if NestTemplate.of_nest(nest) != self:
            raise ValueError("nest structure does not match template")
        return np.asarray(nest.bounds(), np.int64)

    def nest_with(self, bounds) -> LoopNest:
        """Instantiate a concrete LoopNest (unit loops dropped, matching
        what the scalar mapper would have generated)."""
        loops = [Loop(rank=r, bound=int(b), level=lvl, spatial=sp)
                 for (r, lvl, sp), b in zip(self.slots, bounds)
                 if int(b) > 1]
        return LoopNest(loops=tuple(loops), num_levels=self.num_levels)


def template_of(nest: LoopNest) -> NestTemplate:
    return NestTemplate.of_nest(nest)


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TemplateBucket:
    """Padded superset of a family of :class:`NestTemplate`s.

    The bucket fixes only the *shape* of the nest: how many temporal and
    spatial slots each storage level has (``temporal_slots[lvl]`` /
    ``spatial_slots[lvl]``, innermost-first indices) over a rank
    vocabulary ``ranks``.  Which rank each slot iterates is per-candidate
    data (``rank_ids``), and absent loops are unit bounds — so one
    compiled :class:`BucketedModel` evaluates every template the bucket
    :meth:`fits`, across permutations and layers alike.
    """

    ranks: tuple[str, ...]
    temporal_slots: tuple[int, ...]
    spatial_slots: tuple[int, ...]

    def __post_init__(self):
        if len(self.temporal_slots) != len(self.spatial_slots):
            raise ValueError("temporal/spatial slot counts disagree on "
                             "the number of levels")

    @property
    def num_levels(self) -> int:
        return len(self.temporal_slots)

    @property
    def num_slots(self) -> int:
        return sum(self.temporal_slots) + sum(self.spatial_slots)

    def slot_layout(self) -> tuple[tuple[int, bool], ...]:
        """(level, spatial) per slot, outermost level first — each
        level's temporal slots followed by its spatial slots (slot order
        within a level is the loop order; spatial position within the
        level is immaterial to the model)."""
        layout: list[tuple[int, bool]] = []
        for lvl in range(self.num_levels - 1, -1, -1):
            layout += [(lvl, False)] * self.temporal_slots[lvl]
            layout += [(lvl, True)] * self.spatial_slots[lvl]
        return tuple(layout)

    def _offsets(self) -> dict[int, tuple[int, int]]:
        """level -> (first temporal slot, first spatial slot) indices."""
        out: dict[int, tuple[int, int]] = {}
        j = 0
        for lvl in range(self.num_levels - 1, -1, -1):
            out[lvl] = (j, j + self.temporal_slots[lvl])
            j += self.temporal_slots[lvl] + self.spatial_slots[lvl]
        return out

    def fits(self, template: NestTemplate) -> bool:
        """True when every level of ``template`` has no more slots than
        the bucket provides and every rank is in the vocabulary."""
        if template.num_levels != self.num_levels:
            return False
        t = [0] * self.num_levels
        s = [0] * self.num_levels
        for r, lvl, sp in template.slots:
            if r not in self.ranks:
                return False
            (s if sp else t)[lvl] += 1
        return all(t[lvl] <= self.temporal_slots[lvl]
                   and s[lvl] <= self.spatial_slots[lvl]
                   for lvl in range(self.num_levels))

    def lower(self, template: NestTemplate) -> np.ndarray:
        """Bucket slot index of each template slot (order within each
        level preserved; unused bucket slots are left for unit-bound
        padding)."""
        if not self.fits(template):
            raise ValueError(f"template {template} does not fit bucket "
                             f"{self}")
        offs = self._offsets()
        used_t = [0] * self.num_levels
        used_s = [0] * self.num_levels
        out = np.empty(template.num_slots, np.int64)
        for i, (_, lvl, sp) in enumerate(template.slots):
            if sp:
                out[i] = offs[lvl][1] + used_s[lvl]
                used_s[lvl] += 1
            else:
                out[i] = offs[lvl][0] + used_t[lvl]
                used_t[lvl] += 1
        return out

    def lower_population(self, template: NestTemplate, bounds
                         ) -> tuple[np.ndarray, np.ndarray]:
        """Embed a (C, template.num_slots) bound matrix into the bucket:
        returns ``(padded_bounds, rank_ids)``, both (C, num_slots).
        Padding slots carry bound 1 (inert by the lowering contract) and
        rank id 0 (immaterial at bound 1)."""
        bounds = np.atleast_2d(np.asarray(bounds, np.int64))
        slot_map = self.lower(template)
        ridx = {r: i for i, r in enumerate(self.ranks)}
        padded = np.ones((len(bounds), self.num_slots), np.int64)
        padded[:, slot_map] = bounds
        ids = np.zeros(self.num_slots, np.int64)
        ids[slot_map] = [ridx[r] for r, _, _ in template.slots]
        return padded, np.broadcast_to(ids, padded.shape).copy()


@dataclasses.dataclass(frozen=True)
class BucketingPolicy:
    """How templates map to buckets.

    ``pad_temporal_to_ranks`` (the default) pads every level's temporal
    slot count up to the workload's rank count — the shape the genome
    encoding emits — so all free-permutation templates of one workload
    land in ONE bucket and the compile count of a sweep is bounded by the
    number of distinct (workload, spatial shape, num_levels) triples
    rather than the number of loop orders."""

    pad_temporal_to_ranks: bool = True


DEFAULT_BUCKETING = BucketingPolicy()


def bucket_for(template: NestTemplate, ranks,
               policy: BucketingPolicy = DEFAULT_BUCKETING
               ) -> TemplateBucket:
    """The bucket a template lowers into under ``policy``."""
    ranks = tuple(ranks)
    t = [0] * template.num_levels
    s = [0] * template.num_levels
    for r, lvl, sp in template.slots:
        if r not in ranks:
            raise ValueError(f"template rank {r!r} not in {ranks}")
        (s if sp else t)[lvl] += 1
    if policy.pad_temporal_to_ranks:
        t = [max(c, len(ranks)) for c in t]
    return TemplateBucket(ranks=ranks, temporal_slots=tuple(t),
                          spatial_slots=tuple(s))


def group_by_bucket(nests, ranks,
                    policy: BucketingPolicy = DEFAULT_BUCKETING
                    ) -> dict[TemplateBucket, list[int]]:
    """Stable grouping of candidate nests by bucket (the padded analogue
    of :func:`group_by_template`)."""
    groups: dict[TemplateBucket, list[int]] = {}
    for i, nest in enumerate(nests):
        b = bucket_for(template_of(nest), ranks, policy)
        groups.setdefault(b, []).append(i)
    return groups


def lower_nests(bucket: TemplateBucket, nests, idxs
                ) -> tuple[np.ndarray, np.ndarray, list[int]]:
    """Lower the nests at ``idxs`` into ``bucket``: returns
    ``(bounds, rank_ids, order)`` where the two (len(idxs), num_slots)
    arrays are row-aligned with ``order`` (the input indices, regrouped
    by exact template so each template's rows embed in one vectorized
    ``lower_population`` call).  The shared front half of every bucketed
    dispatch (``Sparseloop.evaluate_batch``, ``mapper._search_batched``)."""
    per_template: dict[NestTemplate, list[int]] = {}
    for i in idxs:
        per_template.setdefault(template_of(nests[i]), []).append(i)
    all_bounds, all_ids, order = [], [], []
    for template, t_idxs in per_template.items():
        rows = np.stack([template.bounds_of(nests[i]) for i in t_idxs])
        pb, pi = bucket.lower_population(template, rows)
        all_bounds.append(pb)
        all_ids.append(pi)
        order.extend(t_idxs)
    return np.concatenate(all_bounds), np.concatenate(all_ids), order


# ----------------------------------------------------------------------
def _prod(xs):
    out = 1.0
    for x in xs:
        out = out * x
    return out


def _suffix_any(mask):
    """suffix_any[j] = any(mask[j:]) — the reuse-boundary scan."""
    return jnp.flip(jnp.cumsum(jnp.flip(mask)) > 0)


def _union_b(probs_by_leader: dict):
    keep = 1.0
    for p in probs_by_leader.values():
        keep = keep * (1.0 - p)
    return 1.0 - keep


def _merge_b(dst: dict, leader: str, p) -> None:
    dst[leader] = jnp.maximum(dst.get(leader, 0.0), p)


@dataclasses.dataclass
class _Breakdown:
    actual: object = 0.0
    gated: object = 0.0
    skipped: object = 0.0


# ----------------------------------------------------------------------
# Shared compiled-program registry.  A "program" is the expensive unit
# (trace + XLA compile); it is keyed by (arch TOPOLOGY + SAF structure,
# workload STRUCTURE, caps, template-or-bucket, check_capacity) — never
# by rank bounds, density values, or architecture scalars, which ride
# in as traced WorkloadParams / ArchParams.  Model facades
# (BatchedModel / BucketedModel) bind a concrete (workload, design)'s
# params to a shared program.
# ----------------------------------------------------------------------
@dataclasses.dataclass
class _ProgramRecord:
    """One traced program: the jitted vmapped fn plus its compile
    bookkeeping, shared by every facade whose structure key matches."""

    kind: str
    single: object                     # un-vmapped (batch_args, wp) fn
    fn: object                         # jit(vmap(single, (0, None)))
    sharded_fns: dict = dataclasses.field(default_factory=dict)
    compiled: set = dataclasses.field(default_factory=set)
    #: jitted value_and_grad variants of ``single``, keyed by
    #: (purpose, metric, surrogate, tau) — built lazily by
    #: ``BucketedModel.evaluate_with_arch_grad`` and shared exactly like
    #: ``fn`` (the closure only reads structural attributes)
    grad_fns: dict = dataclasses.field(default_factory=dict)

    def note_compile(self, shape_key) -> bool:
        """First evaluation at a shape is when jit actually compiles.
        Returns True on that first sighting so the caller can attribute
        the evaluation's wall-clock to compile (vs warm-eval) time."""
        with _CACHE_LOCK:
            if shape_key not in self.compiled:
                self.compiled.add(shape_key)
                compile_stats.record_compile(self.kind)
                return True
            return False

    def sharded(self, mesh):
        key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
        with _CACHE_LOCK:
            fn = self.sharded_fns.get(key)
            if fn is None:
                from jax.sharding import PartitionSpec as P
                # batch args (bounds, rank ids, per-candidate arch rows)
                # shard their leading (candidate) axis; the workload
                # params are replicated on every device
                spec = P(mesh.axis_names[0])
                fn = jax.jit(jax.shard_map(
                    jax.vmap(self.single, in_axes=(0, None)),
                    mesh=mesh, in_specs=(spec, P()), out_specs=spec,
                    check_vma=False))
                self.sharded_fns[key] = fn
            return fn


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_CAP = 128

#: guards _PROGRAM_CACHE / _MODEL_CACHE lookup-and-insert plus the
#: per-record compile bookkeeping: the caches are process-global and the
#: DSE service's clients (and any direct caller on another thread) may
#: race a facade construction — without the lock two threads could trace
#: the same program twice and the compile-count CI gates would flake.
#: An RLock because a facade constructor under _CACHE_LOCK re-enters
#: _init_program.
_CACHE_LOCK = threading.RLock()


class _TracedNestModel:
    """Shared traced three-step program over a static slot *shape*.

    The per-candidate inputs are the slot bounds ``b`` and a per-slot
    rank one-hot matrix ``oh`` (num_slots x num_ranks) — which rank each
    slot iterates.  :class:`BatchedModel` closes over a constant ``oh``
    (exact template), :class:`BucketedModel` traces it from per-candidate
    rank ids (padded bucket).  Everything rank-keyed in the scalar model
    (tile bounds, relevance, leader windows) becomes a length-R vector
    masked by ``oh``; unit-bound slots are inert regardless of their rank
    id, which is what makes bucket padding free.
    """

    kind = "program"

    def __init__(self, design, workload: Workload,
                 slot_levels: tuple[int, ...],
                 slot_spatial: tuple[bool, ...], num_levels: int,
                 check_capacity: bool = True,
                 caps: DensityCaps | None = None):
        arch: Architecture = design.arch
        if num_levels != arch.num_levels:
            raise ValueError(
                f"nest shape has {num_levels} levels, architecture "
                f"{arch.name} has {arch.num_levels}")
        self.design = design
        self.arch = arch
        self.safs: SAFSpec = design.safs
        self.workload = workload
        self.slot_levels = tuple(slot_levels)
        self.slot_spatial = tuple(slot_spatial)
        self.num_slots = len(slot_levels)
        self.check_capacity = check_capacity
        self.level_names = [arch.level(s).name
                            for s in range(arch.num_levels)]
        self.ranks: tuple[str, ...] = tuple(workload.rank_bounds)
        self._ridx = {r: i for i, r in enumerate(self.ranks)}
        self._rel = {
            t.name: np.asarray([r in t.ranks for r in self.ranks])
            for t in workload.tensors
        }
        self._tidx = {t.name: i for i, t in enumerate(workload.tensors)}
        # this facade's traced workload inputs (kind ids, parameter
        # vectors, histograms, rank bounds) — the per-layer data bound
        # to the structure-shared program at evaluation time
        self.workload_params = pack_workload_params(workload, caps)
        self.caps = self.workload_params.caps
        # ... and its traced architecture inputs (capacities, bandwidths,
        # energies, PE counts) — the per-design data bound the same way
        self.arch_params = pack_arch_params(arch)
        self.arch_key = arch_structure(arch)
        self._stats = TracedDensityStats(self.caps)
        self._prog: _ProgramRecord | None = None
        self.program_shared = False

    # ------------------------------------------------------------------
    def _init_program(self, token) -> None:
        """Fetch or create the shared compiled program.  ``token``
        completes the structural identity (the exact template for
        BatchedModel — its rank one-hot is a trace constant — or the
        bucket for BucketedModel).

        The record's traced closure is bound to a *detached* shallow
        copy of this facade with the per-layer/per-design state
        stripped: the trace only reads structural attributes (slot
        shape, rel masks, stats, one-hot), so the cache must not pin
        this facade's workload_params / arch_params / histograms for
        the program's lifetime."""
        import copy
        # keyed by the canonical TOPOLOGY KEY (level names + compute
        # name + SAF placement — what shapes the trace), never by the
        # arch's scalar provisioning: capacities / bandwidths / energies
        # ride in as traced ArchParams, so a design sweep shares
        # programs and a mixed-topology population costs O(groups)
        key = (topology_key(self.design.arch, self.safs),
               workload_structure(self.workload),
               self.caps, self.check_capacity, token)
        with _CACHE_LOCK:
            rec = _PROGRAM_CACHE.get(key)
            if rec is None:
                with obs.span("engine.program", kind=self.kind,
                              workload=self.workload.name):
                    host = copy.copy(self)
                    host.workload_params = None  # drop the heavy arrays
                    host.arch_params = None
                    host._prog = None
                    rec = _ProgramRecord(
                        kind=self.kind, single=host._vmapped,
                        fn=jax.jit(jax.vmap(host._vmapped,
                                            in_axes=(0, None))))
                compile_stats.record_program(self.kind)
                if len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_CAP:
                    _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
                _PROGRAM_CACHE[key] = rec
            else:
                compile_stats.record_program_share(rec.kind)
                self.program_shared = True
            self._prog = rec

    def _bind_params(self, workload_params: WorkloadParams | None
                     ) -> tuple:
        """Validate and lower the workload params to jnp leaves."""
        wp = workload_params or self.workload_params
        if wp.caps != self.caps:
            raise ValueError(
                f"workload_params caps {wp.caps} != program caps "
                f"{self.caps}; pack with the program's caps "
                f"(common_caps of the sweep)")
        if wp.structure and wp.structure != workload_structure(
                self.workload):
            raise ValueError(
                "workload_params were packed for a different workload "
                "structure (rank names / projections / output) than "
                "this program's — metrics would be silently wrong")
        if len(wp.rank_bounds) != len(self.ranks) or \
                len(wp.model_ids) != len(self.workload.tensors):
            raise ValueError("workload_params shape does not match the "
                             "program's workload structure")
        return wp.device_leaves()

    def _bind_arch(self, arch_params: ArchParams | None, n: int) -> tuple:
        """Validate arch params against the program's topology and
        broadcast them along the candidate axis: the traced program
        takes one scalar row per candidate, so an unbatched params
        object (one design for the whole population — the facade's own
        arch by default) broadcasts, while a batched one binds one
        design point per candidate (mixed-design co-search)."""
        ap = arch_params or self.arch_params
        if ap.structure and ap.structure != self.arch_key:
            raise ValueError(
                "arch_params were packed for a different architecture "
                "topology (level names / compute) than this program's "
                f"({ap.structure} != {self.arch_key}) — metrics would "
                "be silently wrong")
        S = self.arch.num_levels
        if ap.storage.shape[-2:] != (S, len(STORAGE_FIELDS)):
            raise ValueError(
                f"arch_params storage shape {ap.storage.shape} does not "
                f"match the program's {S} storage levels")
        storage, comp = ap.leaves()
        if ap.batched:
            if len(storage) != n:
                raise ValueError(
                    f"batched arch_params carry {len(storage)} candidate "
                    f"rows, population has {n}")
        else:
            storage = np.broadcast_to(storage, (n,) + storage.shape)
            comp = np.broadcast_to(comp, (n,) + comp.shape)
        return (np.asarray(storage, np.float64),
                np.asarray(comp, np.float64))

    @staticmethod
    def _pad_to_multiple(arrs, n: int):
        """Pad the candidate axis of each array to a multiple of n by
        repeating the last row; returns (padded_arrays, original_C)."""
        C = len(arrs[0])
        pad = (-C) % n
        if pad:
            arrs = [np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
                    for a in arrs]
        return arrs, C

    def _run(self, fn, batch_args, wp, shape_key,
             n: int) -> dict[str, np.ndarray]:
        """Invoke the compiled program and attribute its wall-clock.

        The first (program, shape) sighting is when jit actually
        compiles (``note_compile``), so that call's seconds are compile
        time (``compile_stats.compile_seconds``, span ``engine.compile``)
        while every later call at the shape is warm device time
        (``eval_seconds``, span ``engine.eval``).  The ``np.asarray``
        conversion blocks on the device result, so the measured interval
        is host->device->host inclusive."""
        is_new = self._prog.note_compile(shape_key)
        name = "engine.compile" if is_new else "engine.eval"
        t0 = time.perf_counter()
        with obs.span(name, kind=self.kind,
                      workload=self.workload.name, candidates=n,
                      shape=shape_key):
            out = fn(batch_args, wp)
            out = {k: np.asarray(v) for k, v in out.items()}
        dt = time.perf_counter() - t0
        if is_new:
            compile_stats.record_compile_seconds(dt)
        else:
            compile_stats.record_eval_seconds(dt)
        return out

    # ------------------------------------------------------------------
    # The traced per-candidate program.  Mirrors analyze_dataflow /
    # analyze_sparse / evaluate_microarch line by line; any change to the
    # scalar model must be reflected here (the parity suites pin it).
    # ------------------------------------------------------------------
    def _single(self, b, oh, wp, ap):
        return HyperBatch.run(
            lambda hyper: self._single_pass(b, oh, wp, ap, hyper))

    def _single_pass(self, b, oh, wp, ap, hyper):
        wl = self.workload
        levels = self.slot_levels
        S = self.arch.num_levels
        R = len(self.ranks)
        rel_of = self._rel
        expanded = self.safs.expand_double_sided()
        zname = wl.output

        # traced workload data: rank bounds + per-tensor density params
        rb, mids, dparams, hists = wp
        # traced architecture data: per-level scalar rows (STORAGE_FIELDS
        # columns, innermost-first) + the compute vector (COMPUTE_FIELDS)
        storage, comp = ap
        stats = self._stats
        tidx = self._tidx

        def d_pe(name, tile):
            i = tidx[name]
            return stats.prob_empty(mids[i], dparams[i], hists[i], tile,
                                    hyper)

        def d_ed(name, tile):
            i = tidx[name]
            return stats.expected_density(mids[i], dparams[i], hists[i],
                                          tile)

        def d_mx(name, tile):
            i = tidx[name]
            return stats.max_nnz(mids[i], dparams[i], hists[i], tile)

        def total_size(t: TensorSpec):
            """Traced ``t.size(rank_bounds)`` from the bounds vector."""
            return _prod(
                sum(rb[self._ridx[r]] for r in dim) - (len(dim) - 1)
                for dim in t.projection)

        temporal = [j for j in range(self.num_slots)
                    if not self.slot_spatial[j]]
        spatial = [j for j in range(self.num_slots) if self.slot_spatial[j]]

        def spatial_at(level):
            return [j for j in spatial if levels[j] == level]

        def instances_of(level):
            return _prod(b[j] for j in spatial if levels[j] > level)

        def rank_is(j, rel_vec):
            """Is slot j's rank relevant to ``rel_vec``? (traced bool)"""
            return jnp.any(oh[j] & rel_vec)

        def masked_prod(js):
            """Per-rank bound product over a static slot subset: the
            vectorized form of the rank-keyed tile-bound dicts."""
            if not js:
                return jnp.ones(R)
            sel = np.asarray(js)
            return jnp.prod(jnp.where(oh[sel], b[sel][:, None], 1.0),
                            axis=0)

        # ---------------- step 1: dataflow (dense traffic) ----------------
        def fetch_counts(child_level, rel_vec):
            """(rounds, distinct) tile-fetch counts into child_level; the
            reuse prefix ends at the innermost relevant *non-unit* loop."""
            js = [j for j in temporal if levels[j] > child_level]
            if not js:
                return 1.0, 1.0
            sel = np.asarray(js)
            bs = b[sel]
            rel_arr = jnp.any(oh[sel] & rel_vec, axis=1)
            in_prefix = _suffix_any(rel_arr & (bs > 1))
            rounds = jnp.prod(jnp.where(in_prefix, bs, 1.0))
            distinct = jnp.prod(jnp.where(in_prefix & rel_arr, bs, 1.0))
            return rounds, distinct

        # per-level resident-tile bounds as (R,) vectors — independent of
        # the tensor, so hoisted out of the per-tensor loop
        tbv = [masked_prod([j for j in range(self.num_slots)
                            if levels[j] <= s]) for s in range(S)]
        ones_r = jnp.ones(R)

        def tile_dims(t: TensorSpec, tb):
            return tuple(
                sum(tb[self._ridx[r]] for r in dim) - (len(dim) - 1)
                for dim in t.projection)

        def tile_size(t: TensorSpec, tb):
            return _prod(tile_dims(t, tb))

        total_temporal = _prod(b[j] for j in temporal)
        total_spatial = _prod(b[j] for j in spatial)
        dense_computes = total_temporal * total_spatial

        dense: dict[tuple[str, int], dict] = {}
        for t in wl.tensors:
            rel = rel_of[t.name]
            is_out = t.name == zname
            for s in range(S):
                tb = tbv[s]
                tdims = tile_dims(t, tb)
                tsize = _prod(tdims)
                tl = dict(tile_dims=tdims, tile_size=tsize,
                          fill_words=0.0, partial_fill_words=0.0,
                          read_words=0.0, read_rounds=1.0,
                          update_words=0.0, rmw_read_words=0.0,
                          writeback_words=0.0,
                          instances=instances_of(s))

                rounds, distinct = fetch_counts(s, rel)
                if s < S - 1:
                    if not is_out:
                        tl["fill_words"] = rounds * tsize
                    else:
                        tl["partial_fill_words"] = (rounds - distinct) * tsize

                child = s - 1
                child_tb = tbv[child] if child >= 0 else ones_r
                c_rounds, c_distinct = fetch_counts(child, rel)
                served_tb = child_tb
                for j in spatial_at(s):
                    served_tb = served_tb * jnp.where(oh[j] & rel, b[j],
                                                      1.0)
                served_words = tile_size(t, served_tb)
                tl["read_rounds"] = c_rounds
                if not is_out:
                    tl["read_words"] = c_rounds * served_words
                else:
                    child_tile = tile_size(t, child_tb)
                    spatial_rel = _prod(
                        jnp.where(rank_is(j, rel), b[j], 1.0)
                        for j in spatial_at(s))
                    tl["read_words"] = ((c_rounds - c_distinct) * child_tile
                                        * spatial_rel if s > 0 else 0.0)

                if is_out:
                    fanout = _prod(b[j] for j in spatial_at(s))
                    if s == 0:
                        tl["update_words"] = (total_temporal
                                              * jnp.maximum(1.0, fanout))
                    else:
                        ce, _cd = fetch_counts(s - 1, rel)
                        child_tile = tile_size(t, tbv[s - 1])
                        tl["update_words"] = fanout * ce * child_tile
                    if s < S - 1:
                        tl["rmw_read_words"] = jnp.maximum(
                            0.0, tl["update_words"] - distinct * tsize)
                        tl["writeback_words"] = rounds * tsize
                    else:
                        tl["rmw_read_words"] = jnp.maximum(
                            0.0, tl["update_words"]
                            - total_size(t)
                            / jnp.maximum(1.0, tl["instances"]))

                dense[(t.name, s)] = tl

        # ---------------- step 2: sparse filtering ----------------
        def leader_window_bounds(level, follower_rel):
            """Per-rank leader-intersection window (dataflow.
            leader_tile_bounds), with unit loops treated as absent."""
            bounds = masked_prod([j for j in range(self.num_slots)
                                  if levels[j] < level])
            outer = [j for j in temporal if levels[j] >= level]
            if outer:
                sel = np.asarray(outer)
                bs = b[sel]
                rels = jnp.any(oh[sel] & follower_rel, axis=1)
                include = ~_suffix_any(rels & (bs > 1))
                bounds = bounds * jnp.prod(
                    jnp.where(oh[sel] & include[:, None], bs[:, None],
                              1.0), axis=0)
            return bounds

        def leader_prob(follower: TensorSpec, level_idx, lname: str):
            leader = wl.tensor(lname)
            bounds = leader_window_bounds(level_idx, rel_of[follower.name])
            tile = jnp.maximum(1.0, tile_size(leader, bounds))
            return d_pe(lname, tile)

        skip_ev: dict[tuple[str, int], dict] = {}
        gate_ev: dict[tuple[str, int], dict] = {}
        comp_skip_ev: dict[str, float] = {}
        comp_gate_ev: dict[str, float] = {}

        for saf in expanded:
            if saf.level == "compute":
                for lname in saf.leaders:
                    p = 1.0 - d_ed(lname, 1.0)
                    dst = (comp_skip_ev if saf.kind == SAFKind.SKIP
                           else comp_gate_ev)
                    _merge_b(dst, lname, p)
                continue
            lvl = self.level_names.index(saf.level)
            key = (saf.follower, lvl)
            follower = wl.tensor(saf.follower)
            for lname in saf.leaders:
                p = leader_prob(follower, lvl, lname)
                dst = skip_ev if saf.kind == SAFKind.SKIP else gate_ev
                dst.setdefault(key, {})
                _merge_b(dst[key], lname, p)

        local: dict[tuple[str, int], tuple] = {}
        for t in wl.tensors:
            for s in range(S):
                sk = _union_b(skip_ev.get((t.name, s), {}))
                gt = jnp.maximum(
                    0.0, _union_b({**gate_ev.get((t.name, s), {}),
                                   **skip_ev.get((t.name, s), {})}) - sk)
                local[(t.name, s)] = (sk, gt)

        z_round: dict[int, tuple] = {}
        for s in range(S):
            r_skip: dict[str, object] = {}
            r_gate: dict[str, object] = {}
            for saf in expanded:
                if saf.follower != zname or saf.level == "compute":
                    continue
                for lname in saf.leaders:
                    leader = wl.tensor(lname)
                    bounds = leader_window_bounds(s + 1, rel_of[zname])
                    tile = jnp.maximum(1.0, tile_size(leader, bounds))
                    p = d_pe(lname, tile)
                    dst = r_skip if saf.kind == SAFKind.SKIP else r_gate
                    _merge_b(dst, lname, p)
            sk = _union_b(r_skip)
            gt = jnp.maximum(0.0, _union_b({**r_gate, **r_skip}) - sk)
            z_round[s] = (sk, gt)

        live_frac: dict[tuple[str, int], object] = {}
        gated_from_above: dict[tuple[str, int], object] = {}
        for t in wl.tensors:
            not_skipped, live = 1.0, 1.0
            for s in range(S - 1, -1, -1):
                live_frac[(t.name, s)] = live
                gated_from_above[(t.name, s)] = not_skipped - live
                sk, gt = local[(t.name, s)]
                not_skipped = not_skipped * (1.0 - sk)
                live = live * jnp.maximum(0.0, 1.0 - sk - gt)
            live_frac[(t.name, -1)] = live
            gated_from_above[(t.name, -1)] = not_skipped - live

        impl_skip0: dict[str, object] = {}
        impl_gate0: dict[str, object] = {}
        for t in wl.tensors:
            for s in range(S):
                for lname, p in skip_ev.get((t.name, s), {}).items():
                    _merge_b(impl_skip0, lname, p)
                for lname, p in gate_ev.get((t.name, s), {}).items():
                    _merge_b(impl_gate0, lname, p)
        for lname, p in comp_skip_ev.items():
            _merge_b(impl_skip0, lname, p)
        for lname, p in comp_gate_ev.items():
            _merge_b(impl_gate0, lname, p)
        c_skip = _union_b(impl_skip0)
        c_gate = jnp.maximum(
            0.0, _union_b({**impl_gate0, **impl_skip0}) - c_skip)
        c_act = jnp.maximum(0.0, 1.0 - c_skip - c_gate)

        # ---- format analyzer (formats.analyze_tile_format, traced) ----
        def fmt_stats(fmt, dims, tname: str):
            dims = list(dims) or [1.0]
            nfr = len(fmt.rank_formats)
            if len(dims) < nfr:
                dims = [1.0] * (nfr - len(dims)) + dims
            elif len(dims) > nfr:
                head = _prod(dims[: len(dims) - nfr + 1])
                dims = [head] + dims[len(dims) - nfr + 1:]
            tsize = _prod(dims)
            payload = [_prod(dims[i + 1:]) for i in range(len(dims))]

            meta_avg = meta_max = 0.0
            fibers_avg, fibers_max = 1.0, 1.0
            for i, (rf, d, sz) in enumerate(
                    zip(fmt.rank_formats, dims, payload)):
                coords_avg = fibers_avg * d
                coords_max = fibers_max * d
                p_ne = 1.0 - d_pe(tname, jnp.maximum(1.0, sz))
                n_blocks = _prod(dims[: i + 1])
                occ_avg = jnp.minimum(coords_avg, n_blocks * p_ne)
                occ_max = jnp.maximum(0.0, jnp.minimum(
                    coords_max,
                    jnp.ceil(d_mx(tname, tsize)
                             / jnp.maximum(1.0, sz))))

                cb = float(fmt.coord_bits)
                if rf == RankFormat.U:
                    bits_avg = bits_max = 0.0
                    occ_avg, occ_max = coords_avg, coords_max
                elif rf in (RankFormat.B, RankFormat.UB):
                    bits_avg = fibers_avg * d
                    bits_max = fibers_max * d
                    if rf == RankFormat.UB:
                        occ_avg, occ_max = coords_avg, coords_max
                elif rf in (RankFormat.CP, RankFormat.RLE):
                    bits_avg = occ_avg * cb
                    bits_max = occ_max * cb
                elif rf == RankFormat.UOP:
                    bits_avg = fibers_avg * 2.0 * cb
                    bits_max = fibers_max * 2.0 * cb
                else:  # pragma: no cover
                    raise BatchedUnsupported(f"rank format {rf}")
                meta_avg = meta_avg + bits_avg
                meta_max = meta_max + bits_max
                fibers_avg, fibers_max = occ_avg, occ_max

            if fmt.is_uncompressed:
                data_avg = data_max = tsize * 1.0
            else:
                data_avg = jnp.minimum(
                    tsize * 1.0, d_ed(tname, tsize) * tsize)
                data_max = jnp.minimum(tsize * 1.0, d_mx(tname, tsize))
            return dict(meta_avg=meta_avg, meta_max=meta_max,
                        data_avg=data_avg, data_max=data_max,
                        tile_size=tsize)

        # ---- per-(tensor, level) sparse assembly ----
        sparse: dict[tuple[str, int], dict] = {}
        for t in wl.tensors:
            is_out = t.name == zname
            for s in range(S):
                tl = dense[(t.name, s)]
                fmt = self.safs.format_for(self.level_names[s], t.name)
                fs = fmt_stats(fmt, tl["tile_dims"], t.name)

                live = live_frac[(t.name, s)]
                g_above = gated_from_above[(t.name, s)]
                sk, gt = local[(t.name, s)]
                act_f = live * jnp.maximum(0.0, 1.0 - sk - gt)
                gate_f = live * gt + g_above
                skip_f = jnp.maximum(0.0, 1.0 - act_f - gate_f)
                a_act = live
                a_gate = g_above
                a_skip = jnp.maximum(0.0, 1.0 - a_act - a_gate)

                density_scale = (fs["data_avg"]
                                 / jnp.maximum(1.0, fs["tile_size"])
                                 if fmt.compressed else 1.0)

                def bd(dense_words, fr=None,
                       _fr0=(act_f, gate_f, skip_f), _ds=density_scale):
                    fa, fg, fsk = fr if fr else _fr0
                    moved = dense_words * _ds
                    return _Breakdown(actual=moved * fa, gated=moved * fg,
                                      skipped=moved * fsk)

                if is_out:
                    if s == 0:
                        upd_fr = (c_act, c_gate, c_skip)
                    else:
                        live_c = live_frac[(t.name, s - 1)]
                        g_c = gated_from_above[(t.name, s - 1)]
                        sk_c, gt_c = z_round[s - 1]
                        ac = live_c * jnp.maximum(0.0, 1.0 - sk_c - gt_c)
                        gc = live_c * gt_c + g_c
                        upd_fr = (ac, gc, jnp.maximum(0.0, 1.0 - ac - gc))
                    updates = bd(tl["update_words"], upd_fr)
                    distinct_words = (tl["update_words"]
                                      - tl["rmw_read_words"])
                    rmw = jnp.maximum(0.0, updates.actual - distinct_words)
                    sk_r, gt_r = z_round[s]
                    wa = live * jnp.maximum(0.0, 1.0 - sk_r - gt_r)
                    wg = live * gt_r + g_above
                    wb_fr = (wa, wg, jnp.maximum(0.0, 1.0 - wa - wg))
                    wb = bd(tl["writeback_words"], wb_fr)
                    pf = bd(tl["partial_fill_words"], wb_fr)
                    reads = _Breakdown(actual=wb.actual + rmw,
                                       gated=wb.gated, skipped=wb.skipped)
                    fills = pf
                else:
                    reads = bd(tl["read_words"])
                    fills = bd(tl["fill_words"], (a_act, a_gate, a_skip))
                    updates = _Breakdown()

                meta_per_word = (fs["meta_avg"]
                                 / jnp.maximum(1e-9, fs["data_avg"])
                                 / WORD_BITS)
                has_meta = fs["meta_avg"] > 0
                meta_reads = jnp.where(
                    has_meta, (reads.actual + reads.gated) * meta_per_word,
                    0.0)
                meta_fills = jnp.where(
                    has_meta,
                    (fills.actual + fills.gated
                     + updates.actual + updates.gated) * meta_per_word,
                    0.0)

                sparse[(t.name, s)] = dict(
                    reads=reads, fills=fills, updates=updates,
                    meta_reads=meta_reads, meta_fills=meta_fills,
                    occ_max=fs["data_max"] + fs["meta_max"] / WORD_BITS,
                    instances=tl["instances"])

        # ---- intersection-check overhead (leader metadata scans) ----
        for saf in expanded:
            if saf.level == "compute":
                continue
            lvl = self.level_names.index(saf.level)
            follower = wl.tensor(saf.follower)
            rounds = dense[(saf.follower, lvl)]["read_rounds"]
            for lname in saf.leaders:
                leader = wl.tensor(lname)
                bounds = leader_window_bounds(lvl, rel_of[follower.name])
                ldims = tile_dims(leader, bounds)
                lfmt = self.safs.format_for(self.level_names[lvl], lname)
                ls = fmt_stats(lfmt, ldims, lname)
                bits = jnp.where(ls["meta_avg"] > 0, ls["meta_avg"],
                                 ls["tile_size"] * 1.0)
                sparse[(saf.follower, lvl)]["meta_reads"] = (
                    sparse[(saf.follower, lvl)]["meta_reads"]
                    + rounds * bits / WORD_BITS)

        compute_actual = dense_computes * c_act
        compute_gated = dense_computes * c_gate
        compute_skipped = dense_computes * c_skip

        # ---------------- step 3: micro-architecture ----------------
        valid = jnp.asarray(True)
        energy = 0.0
        worst_cycles = 0.0
        occupancies = []
        for s in range(S):
            cap, bw, e_read, e_write, e_gated, e_meta = (
                storage[s, c] for c in range(len(STORAGE_FIELDS)))
            ra = rg = wa = wg = meta = occ = 0.0
            inst = 1.0
            for t in wl.tensors:
                st = sparse[(t.name, s)]
                inst = jnp.maximum(inst, st["instances"])
                ra = ra + st["reads"].actual
                rg = rg + st["reads"].gated
                wa = wa + st["fills"].actual + st["updates"].actual
                wg = wg + st["fills"].gated + st["updates"].gated
                meta = meta + st["meta_reads"] + st["meta_fills"]
                occ = occ + st["occ_max"]
            occupancies.append(occ * jnp.ones(()))
            if self.check_capacity:
                # traced capacity: an infinite level passes trivially,
                # matching the scalar engine's skip-inf-levels behavior
                valid = valid & (occ <= cap)
            energy = energy + inst * (
                ra * e_read + wa * e_write + (rg + wg) * e_gated
                + meta * e_meta)
            cyc = (ra + rg + wa + wg + meta) / bw
            worst_cycles = jnp.maximum(worst_cycles, cyc)

        pe_inst, pe_mac_e, pe_gated_e, pe_throughput = (
            comp[c] for c in range(len(COMPUTE_FIELDS)))
        n_inst = jnp.clip(total_spatial * 1.0, 1.0, pe_inst)
        compute_cycles = ((compute_actual + compute_gated)
                          / (n_inst * pe_throughput))
        energy = energy + (compute_actual * pe_mac_e
                           + compute_gated * pe_gated_e)
        cycles = jnp.maximum(worst_cycles, compute_cycles)

        return {
            "cycles": cycles,
            "energy_pj": energy,
            "edp": cycles * energy,
            "valid": valid,
            "compute_actual": compute_actual,
            "compute_gated": compute_gated,
            "compute_skipped": compute_skipped,
            "dense_computes": dense_computes * jnp.ones(()),
            # per-storage-level words held at peak (innermost-first):
            # what the capacity check compares against, exposed so the
            # differentiable path can build a smooth capacity surrogate
            "occupancy": jnp.stack(occupancies),
        }


class BatchedModel(_TracedNestModel):
    """Compiled batched evaluator for one (design, workload, template).

    ``evaluate(bounds)`` takes an (C, num_slots) integer array of per-slot
    loop bounds and returns per-candidate metric arrays.  The jitted
    program is cached on the instance; reuse the instance across calls
    (``Sparseloop.evaluate_batch`` and ``mapper.search`` do).
    """

    kind = "template"

    def __init__(self, design, workload: Workload, template: NestTemplate,
                 check_capacity: bool = True,
                 caps: DensityCaps | None = None):
        super().__init__(
            design, workload,
            slot_levels=tuple(lvl for _, lvl, _ in template.slots),
            slot_spatial=tuple(sp for _, _, sp in template.slots),
            num_levels=template.num_levels,
            check_capacity=check_capacity, caps=caps)
        self.template = template
        for r, _, _ in template.slots:
            if r not in self._ridx:
                raise ValueError(f"template rank {r!r} not in workload "
                                 f"ranks {self.ranks}")
        self._onehot = np.asarray(
            [[rr == r for rr in self.ranks] for r, _, _ in template.slots],
            dtype=bool).reshape(self.num_slots, len(self.ranks))
        self._init_program(("template", template))

    def _vmapped(self, args, wp):
        b, ap = args
        return self._single(b, self._onehot, wp, ap)

    # ------------------------------------------------------------------
    def evaluate(self, bounds, mesh=None,
                 workload_params: WorkloadParams | None = None,
                 arch_params: ArchParams | None = None
                 ) -> dict[str, np.ndarray]:
        """bounds: (C, num_slots) -> dict of (C,) arrays.

        ``workload_params`` binds a different layer's traced inputs to
        the shared compiled program (defaults to this facade's own
        workload); ``arch_params`` binds a different design's scalars —
        one design for the whole population, or (batched params) one
        per candidate.  With a ``jax.sharding.Mesh`` of > 1 devices, the
        candidate axis is sharded across the mesh's (single) axis with
        ``shard_map`` — each device vmaps its population slice (arch
        rows shard with their candidates, workload params replicate);
        the population is padded (by repeating the last candidate) to a
        multiple of the device count and the padding is stripped from
        the returned arrays.
        """
        bounds = np.asarray(bounds)
        if bounds.ndim != 2 or bounds.shape[1] != self.num_slots:
            raise ValueError(
                f"bounds must be (C, {self.num_slots}), "
                f"got {bounds.shape}")
        with enable_x64():
            wp = self._bind_params(workload_params)
            storage, comp = self._bind_arch(arch_params, len(bounds))
            # count only after the params bound — a rejected population
            # must not inflate the counters the CI gates read
            compile_stats.record_batched_evals(len(bounds),
                                               shared=self.program_shared)
            if mesh is not None and mesh.size > 1:
                (bounds, storage, comp), C = self._pad_to_multiple(
                    [bounds, storage, comp], mesh.size)
                out = self._run(
                    self._prog.sharded(mesh),
                    (jnp.asarray(bounds, jnp.float64),
                     (jnp.asarray(storage), jnp.asarray(comp))), wp,
                    ("sharded", mesh.size, bounds.shape), C)
                return {k: v[:C] for k, v in out.items()}
            return self._run(
                self._prog.fn,
                (jnp.asarray(bounds, jnp.float64),
                 (jnp.asarray(storage), jnp.asarray(comp))), wp,
                bounds.shape, len(bounds))


class BucketedModel(_TracedNestModel):
    """Compiled batched evaluator for one (design, workload, bucket).

    Like :class:`BatchedModel`, but the slot->rank assignment is traced
    per-candidate data: ``evaluate(bounds, rank_ids)`` takes matching
    (C, num_slots) arrays of loop bounds and rank indices (into
    ``bucket.ranks``), so candidates with *different loop orders* — or
    entire different templates the bucket fits — evaluate through this
    one compiled program.  Unit-bound slots are inert whatever their rank
    id, which is what makes the padding free.
    """

    kind = "bucket"

    def __init__(self, design, workload: Workload, bucket: TemplateBucket,
                 check_capacity: bool = True,
                 caps: DensityCaps | None = None):
        layout = bucket.slot_layout()
        super().__init__(
            design, workload,
            slot_levels=tuple(lvl for lvl, _ in layout),
            slot_spatial=tuple(sp for _, sp in layout),
            num_levels=bucket.num_levels,
            check_capacity=check_capacity, caps=caps)
        if tuple(bucket.ranks) != self.ranks:
            raise ValueError(
                f"bucket ranks {bucket.ranks} != workload ranks "
                f"{self.ranks}")
        self.bucket = bucket
        self._init_program(("bucket", bucket))

    def _vmapped(self, args, wp):
        b, ids, ap = args
        oh = ids[:, None] == jnp.arange(len(self.ranks))
        return self._single(b, oh, wp, ap)

    # ------------------------------------------------------------------
    def traced_single(self, b, rank_ids, wp_leaves, ap_rows):
        """The shared program's un-vmapped traced step, exposed for
        external composition: ``search.fused`` embeds it inside its
        ``lax.scan`` body so the whole generation loop (decode ->
        evaluate -> select) is ONE program.  ``b`` / ``rank_ids`` are
        per-candidate (num_slots,) rows, ``wp_leaves`` the bound
        workload leaves (:meth:`_bind_params`), ``ap_rows`` the
        ``(storage (S, F), compute (4,))`` tuple."""
        return self._prog.single((b, rank_ids, ap_rows), wp_leaves)

    def _arch_grad_fn(self, metric: str, surrogate: bool, tau: float):
        """Jitted vmapped ``value_and_grad`` of the traced step w.r.t.
        the per-candidate arch rows, cached on the shared program record
        (the closure reads only structural state, exactly like ``fn``)."""
        key = ("arch_grad", metric, surrogate, tau)
        with _CACHE_LOCK:
            fn = self._prog.grad_fns.get(key)
            if fn is not None:
                return fn
            single = self._prog.single

            def loss_one(ap_rows, b, ids, wp):
                out = single((b, ids, ap_rows), wp)
                if not surrogate:
                    return out[metric], out
                # smooth capacity surrogate: log-metric plus a softplus
                # barrier per storage level.  z = (occ - cap)/(tau*cap)
                # ramps the penalty as occupancy approaches capacity;
                # infinite-capacity levels contribute softplus(-30) ~ 0
                # (jnp.where on both branches keeps the grad NaN-free)
                storage_rows = ap_rows[0]
                cap = storage_rows[:, STORAGE_FIELDS.index(
                    "capacity_words")]
                finite = jnp.isfinite(cap)
                safe = jnp.where(finite, cap, 1.0)
                z = jnp.where(
                    finite, (out["occupancy"] - safe) / (tau * safe),
                    -30.0)
                loss = (jnp.log(jnp.maximum(out[metric], 1e-300))
                        + jnp.sum(jax.nn.softplus(z)))
                return loss, out

            fn = jax.jit(jax.vmap(
                jax.value_and_grad(loss_one, argnums=0, has_aux=True),
                in_axes=(0, 0, 0, None)))
            self._prog.grad_fns[key] = fn
            compile_stats.record_program(f"{self.kind}_grad")
            return fn

    def evaluate_with_arch_grad(self, bounds, rank_ids,
                                arch_params: ArchParams | None = None, *,
                                metric: str = "edp",
                                surrogate: bool = False,
                                tau: float = 0.05,
                                workload_params: WorkloadParams
                                | None = None) -> dict[str, np.ndarray]:
        """Like :meth:`evaluate`, plus the gradient of a per-candidate
        loss w.r.t. the arch scalar rows (ROADMAP item 1: the model is
        differentiable end to end, so this is one ``value_and_grad``
        pass, not a finite-difference sweep).

        ``surrogate=False``: loss is the raw ``metric`` — grads match
        central finite differences of the scalar oracle.
        ``surrogate=True``: loss is ``log(metric)`` plus a smooth
        softplus capacity barrier (temperature ``tau``) — the
        differentiable stand-in for the hard validity mask that the
        hybrid ES+SGD step descends (the hard mask still gates
        fitness).  Returns the :meth:`evaluate` dict extended with
        ``loss`` (C,), ``grad_storage`` (C, S, F) and ``grad_compute``
        (C, 4)."""
        bounds = np.asarray(bounds)
        rank_ids = np.asarray(rank_ids)
        if bounds.ndim != 2 or bounds.shape[1] != self.num_slots:
            raise ValueError(
                f"bounds must be (C, {self.num_slots}), "
                f"got {bounds.shape}")
        if rank_ids.shape != bounds.shape:
            raise ValueError(
                f"rank_ids shape {rank_ids.shape} != bounds shape "
                f"{bounds.shape}")
        with enable_x64():
            wp = self._bind_params(workload_params)
            storage, comp = self._bind_arch(arch_params, len(bounds))
            compile_stats.record_batched_evals(
                len(bounds), shared=self.program_shared)
            fn = self._arch_grad_fn(metric, surrogate, tau)

            def flat(args, w):
                b, ids, ap = args
                (loss, out), grads = fn(ap, b, ids, w)
                return {**out, "loss": loss, "grad_storage": grads[0],
                        "grad_compute": grads[1]}

            out = self._run(
                flat,
                (jnp.asarray(bounds, jnp.float64),
                 jnp.asarray(rank_ids, jnp.int64),
                 (jnp.asarray(storage), jnp.asarray(comp))), wp,
                ("arch_grad", metric, surrogate, tau, bounds.shape),
                len(bounds))
        return out

    # ------------------------------------------------------------------
    def evaluate(self, bounds, rank_ids, mesh=None,
                 workload_params: WorkloadParams | None = None,
                 arch_params: ArchParams | None = None
                 ) -> dict[str, np.ndarray]:
        """(bounds, rank_ids): matching (C, num_slots) arrays -> dict of
        (C,) metric arrays.  ``workload_params`` binds a different
        layer's traced inputs to the shared compiled program (defaults
        to this facade's own workload); ``arch_params`` binds a
        different design's scalars — one design for the whole
        population, or (batched params) one per candidate, so a
        mixed-design co-search population rides this one program;
        ``mesh`` shards the candidate axis exactly as in
        :meth:`BatchedModel.evaluate`."""
        bounds = np.asarray(bounds)
        rank_ids = np.asarray(rank_ids)
        if bounds.ndim != 2 or bounds.shape[1] != self.num_slots:
            raise ValueError(
                f"bounds must be (C, {self.num_slots}), "
                f"got {bounds.shape}")
        if rank_ids.shape != bounds.shape:
            raise ValueError(
                f"rank_ids shape {rank_ids.shape} != bounds shape "
                f"{bounds.shape}")
        if rank_ids.min(initial=0) < 0 or \
                rank_ids.max(initial=0) >= len(self.ranks):
            raise ValueError(f"rank_ids out of range [0, "
                             f"{len(self.ranks)})")
        with enable_x64():
            wp = self._bind_params(workload_params)
            storage, comp = self._bind_arch(arch_params, len(bounds))
            # count only after the params bound — a rejected population
            # must not inflate the counters the CI gates read
            compile_stats.record_batched_evals(len(bounds),
                                               shared=self.program_shared)
            if mesh is not None and mesh.size > 1:
                (bounds, rank_ids, storage, comp), C = \
                    self._pad_to_multiple(
                        [bounds, rank_ids, storage, comp], mesh.size)
                out = self._run(
                    self._prog.sharded(mesh),
                    (jnp.asarray(bounds, jnp.float64),
                     jnp.asarray(rank_ids, jnp.int64),
                     (jnp.asarray(storage), jnp.asarray(comp))), wp,
                    ("sharded", mesh.size, bounds.shape), C)
                return {k: v[:C] for k, v in out.items()}
            return self._run(
                self._prog.fn,
                (jnp.asarray(bounds, jnp.float64),
                 jnp.asarray(rank_ids, jnp.int64),
                 (jnp.asarray(storage), jnp.asarray(comp))), wp,
                bounds.shape, len(bounds))


# ----------------------------------------------------------------------
# Content-keyed facade cache.  Facades are cheap (they pack WorkloadParams
# and bind a shared program); the expensive traced programs live in
# _PROGRAM_CACHE keyed by workload *structure*, so facades for different
# layers of a network automatically share compiled programs.
# ----------------------------------------------------------------------
_MODEL_CACHE: dict = {}
_MODEL_CACHE_CAP = 128


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    if isinstance(x, np.ndarray):
        return ("ndarray", id(x))
    return x


def _cache_key(design, workload: Workload, shape_key,
               check_capacity: bool, caps):
    # the arch is keyed by its CANONICAL post-__post_init__ field tuples
    # (Architecture.canonical), not the dataclass instances: the -1.0
    # derived-default sentinels (write/metadata energies) resolve before
    # keying, so two archs that agree after derivation alias and any
    # real scalar difference (e.g. gated_energy_pj) never reuses a
    # facade built for another design's defaults
    return (design.arch.canonical(), _freeze(design.safs.formats),
            design.safs.actions,
            workload.name, tuple(workload.rank_bounds.items()),
            workload.tensors, workload.output, _freeze(workload.densities),
            shape_key, check_capacity, caps)


def _get_model(cls, design, workload: Workload, shape, check_capacity,
               caps=None):
    key = _cache_key(design, workload, shape, check_capacity, caps)
    with _CACHE_LOCK:
        model = _MODEL_CACHE.get(key)
        if model is None:
            model = cls(design, workload, shape,
                        check_capacity=check_capacity, caps=caps)
            if len(_MODEL_CACHE) >= _MODEL_CACHE_CAP:
                _MODEL_CACHE.pop(next(iter(_MODEL_CACHE)))
            _MODEL_CACHE[key] = model
        else:
            compile_stats.record_cache_hit()
        return model


def get_batched_model(design, workload: Workload, template: NestTemplate,
                      check_capacity: bool = True,
                      caps: DensityCaps | None = None) -> BatchedModel:
    """Memoized :class:`BatchedModel` constructor.  ``caps`` forces the
    static density capacities (pass :func:`common_caps` of a sweep so
    mixed-density layers share one compiled program)."""
    return _get_model(BatchedModel, design, workload, template,
                      check_capacity, caps)


def get_bucketed_model(design, workload: Workload, bucket: TemplateBucket,
                       check_capacity: bool = True,
                       caps: DensityCaps | None = None) -> BucketedModel:
    """Memoized :class:`BucketedModel` constructor.  ``caps`` forces the
    static density capacities (pass :func:`common_caps` of a sweep so
    mixed-density layers share one compiled program)."""
    return _get_model(BucketedModel, design, workload, bucket,
                      check_capacity, caps)


#: extra cache-clear callbacks registered by downstream modules whose
#: caches hold references into _PROGRAM_CACHE records (e.g. the fused
#: search-program cache) — cleared together so a clear_caches() test
#: hook can never leave a dangling program alive through a fused cache
_EXTRA_CACHE_CLEARERS: list = []


def register_cache_clearer(fn) -> None:
    """Register a zero-arg callback to run inside :func:`clear_caches`
    (idempotent per function object)."""
    with _CACHE_LOCK:
        if fn not in _EXTRA_CACHE_CLEARERS:
            _EXTRA_CACHE_CLEARERS.append(fn)


def clear_caches() -> None:
    """Drop the facade and compiled-program caches (a testing hook:
    exact compile-count assertions otherwise depend on process-global
    cache state).  ``compile_stats`` counters are left untouched."""
    with _CACHE_LOCK:
        _MODEL_CACHE.clear()
        _PROGRAM_CACHE.clear()
        for fn in _EXTRA_CACHE_CLEARERS:
            fn()


def group_by_template(nests) -> dict[NestTemplate, list[int]]:
    """Stable grouping of candidate nests by loop structure."""
    groups: dict[NestTemplate, list[int]] = {}
    for i, nest in enumerate(nests):
        groups.setdefault(template_of(nest), []).append(i)
    return groups


def batched_supported(design, workload: Workload) -> bool:
    """True when every tensor's density model has a traceable form.

    Every Table-4 model now does — actual-data lowers through its
    tile-occupancy histogram — so this only rejects unknown density
    specs (and stays as the dispatch guard for future model kinds)."""
    try:
        for t in workload.tensors:
            m = make_density_model(workload.density_spec(t.name),
                                   t.size(workload.rank_bounds))
            if not m.batched:
                return False
    except (BatchedDensityUnsupported, ValueError):
        return False
    return True
