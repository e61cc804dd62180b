"""Device-resident fused ES: the whole generation loop as ONE program.

The host search loop (``runner.run_search``) pays a host<->device round
trip per generation: numpy ask/tell in ``strategies.py``, a host-side
``decode_bucketed``, one dispatch of the bucket program, then argsort
and archive maintenance back on the host.  Everything the cost model
consumes is already traced data (``ArchParams``, ``WorkloadParams``,
bucket-relative ``rank_ids``), so nothing in that loop *needs* the
host: this module re-implements the ES generation step (tournament
selection, factor-swap crossover, per-gene mutation, immigrants, the
``(mu+lambda)`` survivor fold) as ``jax.random`` ops on int32 genome
arrays, decodes genomes to bucket bounds + rank ids with gathers and a
``segment_prod``, embeds the existing traced three-step model
(``BucketedModel.traced_single`` — the SAME shared program record the
host path compiles, so model semantics cannot drift), and wraps the
whole thing in ``lax.scan`` over generations.  One compile and one
dispatch per *chunk* of generations; population state never leaves the
device between generations (carry buffers are donated off-CPU).

Hybrid ES+SGD (ROADMAP item 1b): for co-search genomes
(``CoSearchEncoding``), the scan body optionally takes a Lamarckian
gradient step on the *continuous design genes* after each evaluation —
``jax.value_and_grad`` of a smooth surrogate loss (log-metric plus a
softplus capacity barrier standing in for the hard validity mask) with
respect to the decoded knob values, a log-space step, then a snap back
to the nearest knob step index.  The HARD mask still gates fitness, and
the emitted per-generation metrics always describe the *evaluated*
(pre-nudge) genomes, so the archive and the scalar-oracle validation
walk stay exactly consistent; nudged genomes enter the survivor fold
with their parent's (slightly stale) fitness and are re-evaluated the
moment selection picks them.

Reproducibility contract: a fused run is bit-reproducible from its key
(same key, same chunking => identical trajectories), but it is NOT
genome-for-genome identical to the host loop — both implement the same
(mu+lambda) ES, yet consume the key stream differently.  The CI gate
pins fused-vs-fused determinism and validates fused winners through the
scalar oracle, the same contract host winners carry.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import obs
from ..core import compile_stats
from ..core.arch import COMPUTE_FIELDS, STORAGE_FIELDS, pack_arch_params
from ..core.batched import (BucketedModel, _ProgramRecord,
                            register_cache_clearer)
from .encoding import (COMPUTE_KNOB_LEVEL, CoSearchEncoding,
                       MapspaceEncoding, TopologyCoSearchEncoding)
from .log import GenerationRecord, SearchLog
from .strategies import EvolutionStrategy, init_population

#: leading-axis names of the per-generation scan outputs, in emit order
YS_FIELDS = ("fitness", "cycles", "energy_pj", "edp", "valid", "genomes")
#: per-generation scan outputs in device-archive (``archive_k``) mode —
#: reduced scalars; the population-sized rows stay on device in the
#: carried top-K buffer
YS_TOPK_FIELDS = ("best_fitness", "best_cycles", "best_energy_pj",
                  "best_edp", "valid_count")


def fused_supported(enc: MapspaceEncoding) -> bool:
    """True when every gene family of the encoding has a traced decode.

    Mapping genes always do; co-search design genes do iff every knob
    steps a *traced* arch scalar (a :data:`STORAGE_FIELDS` column or a
    ``ComputeLevel`` field) — a knob on a static field like ``word_bits``
    reshapes the trace itself and must take the host path.  Topology
    genes never do: the level count shapes the trace itself (a mixed-
    topology population needs one program per topology group, not one
    scan), so topology co-search always takes the host loop."""
    if isinstance(enc, TopologyCoSearchEncoding):
        return False
    if not isinstance(enc, CoSearchEncoding):
        return True
    for field, lvl, _ in enc.space.knobs:
        if lvl == COMPUTE_KNOB_LEVEL:
            if field not in COMPUTE_FIELDS:
                return False
        elif field not in STORAGE_FIELDS:
            return False
    return True


def _encoding_key(enc: MapspaceEncoding) -> tuple:
    """Structural identity of everything the traced decode closes over."""
    spatial = enc.cons.spatial or {}
    key = (
        tuple(enc._gene_prime),
        tuple((r, enc._rank_block[r].start, enc._rank_block[r].stop)
              for r in enc.ranks),
        tuple(enc.ranks), enc.num_levels, tuple(enc.perm_levels),
        tuple(sorted((lvl, order)
                     for lvl, order in enc.fixed_order.items())),
        tuple(sorted((lvl, tuple(d.items()))
                     for lvl, d in spatial.items())),
        enc.genome_size,
    )
    if isinstance(enc, CoSearchEncoding):
        key += (enc.num_map_genes, enc.space.knobs,
                enc.base_design.arch.canonical())
    return key


class FusedProgram:
    """One compiled scan-over-generations search program.

    Built by :func:`get_fused_program` for a (bucket program record,
    encoding structure, ES hyper-parameters, metric, SGD config) tuple;
    chunk-length variants jit lazily and compile once per (length,
    pop_size, genome_size) shape.  The carry is
    ``(prng_key, pop (P,G) int32, fit (P,) f64, pending (P,G) int32)``
    — ``pending`` is the not-yet-evaluated child population the next
    generation starts by scoring.

    With ``archive_k > 0`` the carry grows a device-resident top-K
    archive buffer ``(arch_fit (K,) f64, arch_gen (K,G) int32)``: each
    generation merges its evaluated rows into the buffer inside the
    scan (dedup-masked against rows already held), the per-generation
    scan outputs shrink to best-of-generation SCALARS
    (:data:`YS_TOPK_FIELDS`), and the host archive fold ingests K rows
    once per chunk instead of ``pop_size`` rows per generation —
    population-sized data never crosses to the host."""

    def __init__(self, bm: BucketedModel, enc: MapspaceEncoding,
                 strat: EvolutionStrategy, *, metric: str = "edp",
                 sgd_lr: float = 0.0, sgd_tau: float = 0.05,
                 archive_k: int = 0):
        from jax import enable_x64
        with enable_x64():
            self._build(bm, enc, strat, metric=metric, sgd_lr=sgd_lr,
                        sgd_tau=sgd_tau, archive_k=archive_k)

    def _build(self, bm: BucketedModel, enc: MapspaceEncoding,
               strat: EvolutionStrategy, *, metric: str,
               sgd_lr: float, sgd_tau: float, archive_k: int):
        import jax.numpy as jnp

        self.bm = bm
        self.enc = enc
        self.metric = metric
        self.sgd_lr = float(sgd_lr)
        self.sgd_tau = float(sgd_tau)
        self.archive_k = int(archive_k)
        self.pop_size = int(strat.pop_size)
        self.tournament = int(strat.tournament)
        self.crossover_rate = float(strat.crossover_rate)
        self.mutation_rate = float(strat.mutation_rate)
        self.n_immigrants = int(round(strat.immigrants * strat.pop_size))
        self.cosearch = isinstance(enc, CoSearchEncoding)
        if enc.genome_size == 0:
            raise ValueError("fused search needs at least one gene")
        if not fused_supported(enc):
            raise ValueError(
                "encoding has design knobs without a traced decode "
                "(non-ArchParams fields) — use the host search loop")

        #: compile/eval bookkeeping for THIS program family ("fused"
        #: kind), separate from the bucket record it embeds
        self.rec = _ProgramRecord(kind="fused", single=None, fn=None)
        compile_stats.record_program("fused")

        # ---------- static decode tables (trace constants) ----------
        self._card = jnp.asarray(enc.cardinality, jnp.int32)
        self._gene_block = jnp.asarray(enc.gene_block, jnp.int32)
        self.num_blocks = enc.num_blocks
        F, R, L = enc.num_factor_genes, len(enc.ranks), enc.num_levels
        self._F, self._R, self._L = F, R, L
        self._primes = jnp.asarray(enc._gene_prime, jnp.float64)
        seg = np.empty(F, np.int32)
        for ri, r in enumerate(enc.ranks):
            seg[enc._rank_block[r]] = ri
        self._seg_ids = jnp.asarray(seg)
        self._perm_table = jnp.asarray(
            np.asarray(enc.perms, np.int64).reshape(-1, R), jnp.int32)
        ridx = {r: i for i, r in enumerate(enc.ranks)}
        #: per level: a static order row, or the perm-gene index to gather
        self._level_order: list = []
        for lvl in range(L):
            if lvl in enc.fixed_order:
                self._level_order.append(jnp.asarray(
                    [ridx[r] for r in enc.fixed_order[lvl]], jnp.int32))
            else:
                self._level_order.append(
                    F + enc.perm_levels.index(lvl))
        spatial = enc.cons.spatial or {}
        #: outermost-level-first spatial constants, matching the host
        #: decode_bucketed assembly order exactly
        self._spatial = {
            lvl: [(ridx[r], float(b))
                  for r, b in spatial.get(lvl, {}).items() if b > 1]
            for lvl in range(L)}

        # ---------- co-search design-gene tables ----------
        if self.cosearch:
            self.num_map_genes = enc.num_map_genes
            base_arch = enc.base_design.arch
            self._base_params = pack_arch_params(base_arch)
            knobs = enc.space.knobs
            explicit = {(lvl, field) for field, lvl, _ in knobs}
            self._knob_steps = [jnp.asarray(s, jnp.float64)
                                for _, _, s in knobs]
            #: per knob: list of scatter cells ("storage", s, j, coef)
            #: or ("compute", j, coef) — the static mirror of
            #: DesignSpace._replace_level incl. derived-default coupling
            self._knob_cells: list[list[tuple]] = []
            #: knobs the SGD step may move: all-positive step values
            #: (the log-space step needs log(v))
            self._knob_sgd = [all(v > 0 for v in s) for _, _, s in knobs]
            self._knob_log_steps = [
                jnp.log(jnp.asarray(s, jnp.float64)) if ok else None
                for ok, (_, _, s) in zip(self._knob_sgd, knobs)]
            for field, lvl, _ in knobs:
                if lvl == COMPUTE_KNOB_LEVEL:
                    self._knob_cells.append(
                        [("compute", COMPUTE_FIELDS.index(field), 1.0)])
                    continue
                s = base_arch.level_index(lvl)
                cells = [("storage", s, STORAGE_FIELDS.index(field), 1.0)]
                if field == "read_energy_pj":
                    lv = base_arch.level(s)
                    if ((lvl, "write_energy_pj") not in explicit
                            and lv.write_energy_pj == lv.read_energy_pj):
                        cells.append(("storage", s, STORAGE_FIELDS.index(
                            "write_energy_pj"), 1.0))
                    if ((lvl, "metadata_read_energy_pj") not in explicit
                            and lv.metadata_read_energy_pj
                            == 0.25 * lv.read_energy_pj):
                        cells.append(("storage", s, STORAGE_FIELDS.index(
                            "metadata_read_energy_pj"), 0.25))
                self._knob_cells.append(cells)
        else:
            self.num_map_genes = enc.genome_size
            self._base_params = bm.arch_params

        self._chunk_fns: dict[int, object] = {}

    # ------------------------------------------------------------------
    # traced decode: genome -> (bounds, rank_ids) bucket-relative rows
    # ------------------------------------------------------------------
    def _decode_map(self, g):
        """(G,) int32 -> ((num_slots,) f64 bounds, (num_slots,) int32
        rank ids); the traced mirror of ``decode_bucketed`` for one
        candidate."""
        import jax
        import jax.numpy as jnp

        F, R, L = self._F, self._R, self._L
        if F:
            assigned = g[:F, None] == jnp.arange(L, dtype=jnp.int32)
            contrib = jnp.where(assigned, self._primes[:, None], 1.0)
            fb = jax.ops.segment_prod(
                contrib, self._seg_ids, num_segments=R,
                indices_are_sorted=True)          # (R, L) factor bounds
        else:
            fb = jnp.ones((R, L), jnp.float64)
        ids_parts, bound_parts = [], []
        for lvl in range(L - 1, -1, -1):
            order = self._level_order[lvl]
            if isinstance(order, int):            # free level: gathered
                order = self._perm_table[g[order]]
            ids_parts.append(order)
            bound_parts.append(fb[order, lvl])
            for rid, b in self._spatial[lvl]:
                ids_parts.append(jnp.asarray([rid], jnp.int32))
                bound_parts.append(jnp.asarray([b], jnp.float64))
        return (jnp.concatenate(bound_parts),
                jnp.concatenate(ids_parts))

    def _design_vals(self, g):
        """Design-gene row -> (K,) knob values (step-table gathers)."""
        import jax.numpy as jnp
        return jnp.stack([
            steps[g[self.num_map_genes + k]]
            for k, steps in enumerate(self._knob_steps)])

    def _rows_of(self, vals, base_storage, base_comp):
        """Scatter knob values onto the base arch rows — the traced
        mirror of ``DesignSpace.arch_of`` + ``pack_arch_params``."""
        storage, comp = base_storage, base_comp
        for k, cells in enumerate(self._knob_cells):
            for cell in cells:
                if cell[0] == "storage":
                    _, s, j, coef = cell
                    storage = storage.at[s, j].set(coef * vals[k])
                else:
                    _, j, coef = cell
                    comp = comp.at[j].set(coef * vals[k])
        return storage, comp

    # ------------------------------------------------------------------
    def _eval_one(self, g, wp, base_storage, base_comp):
        """Evaluate ONE genome; returns (fitness, cycles, energy, edp,
        valid, possibly-SGD-nudged genome)."""
        import jax
        import jax.numpy as jnp

        g = jnp.mod(g, self._card)
        b, ids = self._decode_map(g)
        single = self.bm.traced_single

        if not self.cosearch:
            out = single(b, ids, wp, (base_storage, base_comp))
            fit = jnp.where(out["valid"], out[self.metric], jnp.inf)
            return (fit, out["cycles"], out["energy_pj"], out["edp"],
                    out["valid"], g)

        vals = self._design_vals(g)
        cap_col = STORAGE_FIELDS.index("capacity_words")

        def loss_fn(v):
            storage, comp = self._rows_of(v, base_storage, base_comp)
            out = single(b, ids, wp, (storage, comp))
            cap = storage[:, cap_col]
            finite = jnp.isfinite(cap)
            safe = jnp.where(finite, cap, 1.0)
            z = jnp.where(
                finite,
                (out["occupancy"] - safe) / (self.sgd_tau * safe), -30.0)
            loss = (jnp.log(jnp.maximum(out[self.metric], 1e-300))
                    + jnp.sum(jax.nn.softplus(z)))
            return loss, out

        if self.sgd_lr <= 0.0:
            _, out = loss_fn(vals)
            fit = jnp.where(out["valid"], out[self.metric], jnp.inf)
            return (fit, out["cycles"], out["energy_pj"], out["edp"],
                    out["valid"], g)

        (_, out), gvals = jax.value_and_grad(
            loss_fn, has_aux=True)(vals)
        fit = jnp.where(out["valid"], out[self.metric], jnp.inf)
        # Lamarckian log-space step, normalized so the largest component
        # moves by exactly sgd_lr log-units, then snapped back to the
        # nearest step index of each (all-positive) knob.  Invalid /
        # non-finite candidates take no step — their gradients may be
        # garbage and their genes should stay searchable by the ES.
        mask = jnp.asarray(self._knob_sgd)
        glog = gvals * vals                       # d loss / d log(v)
        scale = jnp.max(jnp.where(mask, jnp.abs(glog), 0.0)) + 1e-30
        step_ok = out["valid"] & jnp.isfinite(scale)
        u2 = (jnp.log(jnp.where(mask, vals, 1.0))
              - self.sgd_lr * glog / scale)
        g2 = g
        for k, log_steps in enumerate(self._knob_log_steps):
            if log_steps is None:
                continue
            idx = jnp.argmin(jnp.abs(log_steps - u2[k])).astype(g.dtype)
            pos = self.num_map_genes + k
            g2 = g2.at[pos].set(jnp.where(step_ok, idx, g[pos]))
        return (fit, out["cycles"], out["energy_pj"], out["edp"],
                out["valid"], g2)

    # ------------------------------------------------------------------
    # traced ES generation step (mirrors strategies.EvolutionStrategy)
    # ------------------------------------------------------------------
    def _ask(self, key, pop, fit):
        import jax.numpy as jnp
        import jax.random as jrandom

        P, G = self.pop_size, self.enc.genome_size
        ka, kb, kc, kx, km, ki = jrandom.split(key, 6)

        def select(k):
            draws = jrandom.randint(k, (P, self.tournament), 0, P,
                                    dtype=jnp.int32)
            win = jnp.argmin(fit[draws], axis=1)
            return draws[jnp.arange(P), win]

        pa = pop[select(ka)]
        pb = pop[select(kb)]
        do_cross = jrandom.bernoulli(kc, self.crossover_rate, (P,))
        pick = jrandom.bernoulli(kx, 0.5, (P, self.num_blocks))
        crossed = jnp.where(pick[:, self._gene_block], pa, pb)
        children = jnp.where(do_cross[:, None], crossed, pa)
        # mutation: per-gene resample + one forced flip per genome
        k1, k2, k3 = jrandom.split(km, 3)
        flip = jrandom.bernoulli(k1, self.mutation_rate, (P, G))
        forced = jrandom.randint(k2, (P,), 0, G, dtype=jnp.int32)
        flip = flip.at[jnp.arange(P), forced].set(True)
        fresh = jrandom.randint(k3, (P, G), 0, self._card,
                                dtype=jnp.int32)
        children = jnp.where(flip, fresh, children)
        if self.n_immigrants:
            imm = jrandom.randint(ki, (self.n_immigrants, G), 0,
                                  self._card, dtype=jnp.int32)
            children = children.at[-self.n_immigrants:].set(imm)
        return children

    # ------------------------------------------------------------------
    def _chunk_fn(self, length: int):
        import jax
        import jax.numpy as jnp
        import jax.random as jrandom
        from jax import lax

        fn = self._chunk_fns.get(length)
        if fn is not None:
            return fn

        eval_pop = jax.vmap(self._eval_one, in_axes=(0, None, None, None))
        P, K = self.pop_size, self.archive_k

        def run(carry, wp, base_storage, base_comp):
            def body(carry, _):
                if K:
                    key, pop, fit, pending, afit, agen = carry
                else:
                    key, pop, fit, pending = carry
                pf, cyc, en, edp, valid, nudged = eval_pop(
                    pending, wp, base_storage, base_comp)
                if K:
                    # merge PRE-nudge (evaluated) rows into the device
                    # top-K buffer; rows already held (finite slot with
                    # an identical genome) are masked out so the buffer
                    # holds K DISTINCT best rows, matching the host
                    # fold's seen-set dedup
                    dup = jnp.any(
                        jnp.all(pending[:, None, :] == agen[None, :, :],
                                axis=-1)
                        & jnp.isfinite(afit)[None, :], axis=1)
                    cat_f = jnp.concatenate(
                        [afit, jnp.where(dup, jnp.inf, pf)])
                    cat_g = jnp.concatenate([agen, pending])
                    keep = jnp.argsort(cat_f)[:K]
                    afit, agen = cat_f[keep], cat_g[keep]
                    i = jnp.argmin(pf)
                    ys = (pf[i], cyc[i], en[i], edp[i],
                          jnp.sum(valid.astype(jnp.int64)))
                else:
                    # emit PRE-nudge genomes with their true fitness:
                    # the archive and oracle walk must see evaluated
                    # pairs
                    ys = (pf, cyc, en, edp, valid, pending)
                allp = jnp.concatenate([pop, nudged])
                allf = jnp.concatenate([fit, pf])
                order = jnp.argsort(allf)[:P]   # stable (mu+lambda) fold
                pop2, fit2 = allp[order], allf[order]
                key2, ksub = jrandom.split(key)
                nxt = (key2, pop2, fit2, self._ask(ksub, pop2, fit2))
                if K:
                    nxt += (afit, agen)
                return nxt, ys

            return lax.scan(body, carry, None, length=length)

        # donating the carry keeps population state truly device-resident
        # off-CPU; the CPU backend warns on donation, so skip it there
        donate = () if jax.default_backend() == "cpu" else (0,)
        fn = jax.jit(run, donate_argnums=donate)
        self._chunk_fns[length] = fn
        return fn

    # ------------------------------------------------------------------
    def init_carry(self, key):
        """Initial scan carry from an int seed or PRNG key: the host
        strategies' half-structured / half-uniform initial population as
        ``pending``, parents empty (+inf fitness placeholders the first
        survivor fold discards)."""
        import jax.numpy as jnp
        import jax.random as jrandom
        from jax import enable_x64

        if isinstance(key, (int, np.integer)):
            key = jrandom.PRNGKey(int(key))
        with enable_x64():
            key, sub = jrandom.split(key)
            pop0 = self.enc.repair(
                init_population(sub, self.enc, self.pop_size))
            pop0 = jnp.asarray(pop0, jnp.int32)
            fit0 = jnp.full((self.pop_size,), jnp.inf, jnp.float64)
            # pending gets its own buffer: the chunk donates the carry,
            # and one buffer cannot be donated twice
            carry = (key, pop0, fit0, jnp.array(pop0, copy=True))
            if self.archive_k:
                # +inf placeholder rows: the dup mask ignores them
                # (non-finite slot) and every real row sorts above them
                carry += (
                    jnp.full((self.archive_k,), jnp.inf, jnp.float64),
                    jnp.zeros((self.archive_k, self.enc.genome_size),
                              jnp.int32))
            return carry

    def inject(self, carry, genomes, fitness):
        """Host-side migrant fold (island search between chunks): merge
        (genomes, fitness) into the carried population with the same
        stable best-of ``(mu+lambda)`` rule as ``strat.tell``.  The
        device archive buffer (``archive_k`` mode) is left untouched —
        migrants were evaluated on their home island and enter its
        archive there."""
        import jax.numpy as jnp
        from jax import enable_x64

        key, pop, fit, pending, *buffer = carry
        g = self.enc.repair(np.asarray(genomes, np.int64))
        allp = np.concatenate([np.asarray(pop, np.int64), g])
        allf = np.concatenate([np.asarray(fit, np.float64),
                               np.asarray(fitness, np.float64)])
        order = np.argsort(allf, kind="stable")[: self.pop_size]
        with enable_x64():
            return (key, jnp.asarray(allp[order], jnp.int32),
                    jnp.asarray(allf[order], jnp.float64), pending,
                    *buffer)

    # ------------------------------------------------------------------
    def invoke_chunk(self, carry, length: int):
        """Run ``length`` generations in one dispatch.  Returns
        ``(new_carry, ys)`` where ``ys`` maps :data:`YS_FIELDS` to host
        arrays with a leading generation axis.  Compile/eval seconds are
        attributed exactly like the batched evaluators: the first
        (length, pop, genome) shape sighting is an ``engine.compile``
        span + ``compile_seconds``, later calls are ``engine.eval``; in
        either, ``engine.dispatch`` spans the jitted call and
        ``engine.fetch`` the host conversions that wait on it."""
        import jax.numpy as jnp
        from jax import enable_x64

        with enable_x64():
            fn = self._chunk_fn(length)
            wp = self.bm._bind_params(None)
            storage, comp = self._base_params.leaves()
            base_storage = jnp.asarray(storage, jnp.float64)
            base_comp = jnp.asarray(comp, jnp.float64)
            shape_key = (length, self.pop_size, self.enc.genome_size)
            is_new = self.rec.note_compile(shape_key)
            compile_stats.record_batched_evals(
                length * self.pop_size, shared=self.bm.program_shared)
            name = "engine.compile" if is_new else "engine.eval"
            t0 = time.perf_counter()
            with obs.span(name, kind="fused",
                          workload=self.bm.workload.name,
                          candidates=length * self.pop_size,
                          shape=shape_key):
                with obs.span("engine.dispatch"):
                    carry, ys = fn(carry, wp, base_storage, base_comp)
                with obs.span("engine.fetch"):
                    if self.archive_k:
                        ys = {k: np.asarray(v)
                              for k, v in zip(YS_TOPK_FIELDS, ys)}
                        # ONE K-row host crossing per chunk: the
                        # cumulative top-K buffer snapshot (the carry
                        # persists, so this is global-so-far, not
                        # per-chunk)
                        ys["archive_fitness"] = np.asarray(carry[4])
                        ys["archive_genomes"] = np.asarray(carry[5])
                    else:
                        ys = {k: np.asarray(v)
                              for k, v in zip(YS_FIELDS, ys)}
            dt = time.perf_counter() - t0
            if is_new:
                compile_stats.record_compile_seconds(dt)
            else:
                compile_stats.record_eval_seconds(dt)
        return carry, ys


# ----------------------------------------------------------------------
# program cache: fused programs are expensive (one XLA compile per chunk
# shape) and fully determined by (bucket program record, encoding
# structure, ES hyper-parameters, metric, SGD config) — share them the
# way _PROGRAM_CACHE shares bucket programs
# ----------------------------------------------------------------------
_FUSED_CACHE: dict = {}
_FUSED_CACHE_CAP = 64
_FUSED_LOCK = threading.RLock()


def clear_fused_cache() -> None:
    with _FUSED_LOCK:
        _FUSED_CACHE.clear()


register_cache_clearer(clear_fused_cache)


def get_fused_program(bm: BucketedModel, enc: MapspaceEncoding,
                      strat: EvolutionStrategy, *, metric: str = "edp",
                      sgd_lr: float = 0.0,
                      sgd_tau: float = 0.05,
                      archive_k: int = 0) -> FusedProgram:
    """Memoized :class:`FusedProgram` constructor.  Keyed by the
    IDENTITY of the bucket facade's shared program record (which already
    encodes arch topology, SAF structure, workload structure, density
    caps, bucket and check_capacity) plus the encoding structure and
    search hyper-parameters; the cached value holds a strong reference
    to the record, so an id can never be recycled while its entry
    lives."""
    key = (id(bm._prog), _encoding_key(enc), strat.pop_size,
           strat.tournament, strat.crossover_rate, strat.mutation_rate,
           strat.immigrants, metric, float(sgd_lr), float(sgd_tau),
           int(archive_k))
    with _FUSED_LOCK:
        hit = _FUSED_CACHE.get(key)
        if hit is not None:
            rec_ref, fp = hit
            if rec_ref is bm._prog:
                fp.bm = bm   # rebind: same program, freshest facade
                compile_stats.record_program_share("fused")
                return fp
        fp = FusedProgram(bm, enc, strat, metric=metric, sgd_lr=sgd_lr,
                          sgd_tau=sgd_tau, archive_k=archive_k)
        if len(_FUSED_CACHE) >= _FUSED_CACHE_CAP:
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[key] = (bm._prog, fp)
        return fp


# ----------------------------------------------------------------------
class ChunkAbsorber:
    """Host-side fold of fused-chunk outputs into the runner's search
    state: archive, best-so-far, evaluation counters and per-generation
    :class:`SearchLog` records (with ``wall_time_s=None`` — a
    generation inside a compiled scan has no individually measurable
    wall-clock; honest chunk timing lives in ``SearchLog.timing``).
    Mirrors ``runner.run_search``'s host-loop bookkeeping exactly, so
    the scalar-oracle validation walk downstream is path-independent.

    Handles both chunk-output shapes: the legacy full-population ys
    (:data:`YS_FIELDS`) fold per-generation, and the device-archive
    mode (:data:`YS_TOPK_FIELDS` + the K-row buffer snapshot, from a
    program built with ``archive_k > 0``) — which needs ``pop_size``
    to keep the evaluation counters honest."""

    def __init__(self, metric: str, archive_size: int,
                 pop_size: int | None = None):
        self.metric = metric
        self.archive_size = archive_size
        self.pop_size = pop_size
        self.archive_fit: list[float] = []
        self.archive_gen: list[np.ndarray] = []
        self.seen: set[bytes] = set()
        self.best = {"fitness": np.inf, "cycles": np.inf,
                     "energy_pj": np.inf, "edp": np.inf}
        self.n_eval = 0
        self.n_valid = 0
        self.gen = 0

    def absorb(self, ys: dict, log: SearchLog | None = None) -> None:
        if "genomes" not in ys:
            return self._absorb_topk(ys, log)
        fits = np.asarray(ys["fitness"], np.float64)
        genomes = np.asarray(ys["genomes"], np.int64)
        for t in range(len(fits)):
            fitness = fits[t]
            self.n_eval += len(fitness)
            self.n_valid += int(np.asarray(ys["valid"][t]).sum())
            i = int(np.argmin(fitness))
            if fitness[i] < self.best["fitness"]:
                self.best = {
                    "fitness": float(fitness[i]),
                    "cycles": float(ys["cycles"][t][i]),
                    "energy_pj": float(ys["energy_pj"][t][i]),
                    "edp": float(ys["edp"][t][i])}
            for j in np.argsort(fitness,
                                kind="stable")[: self.archive_size]:
                if not np.isfinite(fitness[j]):
                    break
                b = genomes[t, j].tobytes()
                if b not in self.seen:
                    self.seen.add(b)
                    self.archive_fit.append(float(fitness[j]))
                    self.archive_gen.append(genomes[t, j].copy())
            if len(self.archive_fit) > 4 * self.archive_size:
                order = np.argsort(self.archive_fit,
                                   kind="stable")[: self.archive_size]
                self.archive_fit = [self.archive_fit[k] for k in order]
                self.archive_gen = [self.archive_gen[k] for k in order]
            if log is not None:
                log.append(GenerationRecord(
                    generation=self.gen, evaluations=self.n_eval,
                    valid=self.n_valid,
                    best_fitness=self.best["fitness"],
                    best_cycles=self.best["cycles"],
                    best_energy_pj=self.best["energy_pj"],
                    best_edp=self.best["edp"], wall_time_s=None))
            self.gen += 1

    def _absorb_topk(self, ys: dict,
                     log: SearchLog | None = None) -> None:
        """Device-archive fold: per-generation best scalars drive the
        best-so-far trajectory and log records; the archive is the
        cumulative K-row device buffer, REPLACED wholesale each chunk
        (the buffer is global-top-K-so-far, a superset of anything a
        previous chunk delivered)."""
        if self.pop_size is None:
            raise ValueError(
                "ChunkAbsorber needs pop_size to absorb device-archive "
                "(archive_k) chunk outputs")
        bf = np.asarray(ys["best_fitness"], np.float64)
        nv = np.asarray(ys["valid_count"], np.int64)
        for t in range(len(bf)):
            self.n_eval += self.pop_size
            self.n_valid += int(nv[t])
            if bf[t] < self.best["fitness"]:
                self.best = {
                    "fitness": float(bf[t]),
                    "cycles": float(ys["best_cycles"][t]),
                    "energy_pj": float(ys["best_energy_pj"][t]),
                    "edp": float(ys["best_edp"][t])}
            if log is not None:
                log.append(GenerationRecord(
                    generation=self.gen, evaluations=self.n_eval,
                    valid=self.n_valid,
                    best_fitness=self.best["fitness"],
                    best_cycles=self.best["cycles"],
                    best_energy_pj=self.best["energy_pj"],
                    best_edp=self.best["edp"], wall_time_s=None))
            self.gen += 1
        afit = np.asarray(ys["archive_fitness"], np.float64)
        agen = np.asarray(ys["archive_genomes"], np.int64)
        self.archive_fit, self.archive_gen = [], []
        self.seen = set()
        for f, g in zip(afit, agen):
            if not np.isfinite(f):
                break       # placeholder rows sort last
            b = g.tobytes()
            if b in self.seen:
                continue
            self.seen.add(b)
            self.archive_fit.append(float(f))
            self.archive_gen.append(g.copy())
