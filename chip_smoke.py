"""Run the modeler's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: population sharding only

One process drives everything, through the entry points a user calls:

* search  -- ``run_search(strategy="es", fused=True)`` on Table 5 conv2_x
  (3136x576x64, densities 0.4/0.55) on ``scnn_like(three_level_arch())``
  at a population of 1024: one fused compile, no scalar evaluations, a
  winner the scalar oracle confirms and a byte-identical same-key re-run;
* engine  -- one ``BucketedModel.evaluate`` of 1024 decoded candidates
  against the scalar oracle, row by row;
* fleet   -- ``fleet_sweep(["qwen3-4b"], reduced=False)`` within its
  compile bound, a few rows checked against the scalar oracle;
* service -- an ``EvaluationService`` answering two clients, each result
  equal to the direct ``BucketedModel.evaluate``;
* kernels -- every Pallas kernel compiled for the TPU (``interpret=False``,
  ``tpu_custom_call`` in its HLO) against its reference;
* agree   -- the advisor-vs-kernel agreement harness (``validate_fleet``).

With ``--chips 4`` it runs only the host-loop ES with the population
sharded over all four devices, against the same key on one device.

Each phase prints one line: wall seconds (after the results are on the
host), compiles and their seconds, warm seconds, and its parity numbers.
A failed check raises, so the script exits non-zero before its last line,
which is ``{"ok": true, "device": {...}}``.  It refuses to run anywhere
but on a TPU.  The compile cache is placed by ``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: relative deviation the engine must hold against the float64 scalar
#: oracle, on the chip as on the CPU
PARITY_BOUND = 1e-6
POP = 1024
CHUNK = 4
GENERATIONS = 12
SERVICE_BATCH = 256
#: qwen3-4b MLP widths: tokens x d_model x d_ff
KERNEL_MKN = (256, 2560, 9728)
FLASH_SHAPE = (1, 4096, 8, 128)      # (B, S, H, D)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_dev(a, b):
    """Elementwise |a - b| / |b| (0 where both are equal, inf included)."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    same = (a == b) | (np.isnan(a) & np.isnan(b))
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
    return np.where(same, 0.0, r)


def report(phase: str, t0: float, st, **numbers) -> None:
    """One line per phase: wall, compiles, compile and warm seconds, and
    the phase's own numbers (unrounded)."""
    fields = {"wall_s": time.perf_counter() - t0}
    if st is not None:
        fields.update(compiles=st.compiles,
                      compiles_by_kind=dict(st.compiles_by_kind),
                      compile_s=st.compile_seconds, warm_s=st.eval_seconds,
                      scalar_evals=st.scalar_evals)
    fields.update(numbers)
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def conv2_x():
    """Table 5 conv2_x as an im2col GEMM on the SCNN-like three-level
    design, spatial n=8: the space ``benchmarks/bench_fused.py`` runs."""
    from repro.core import matmul
    from repro.core.mapper import MapspaceConstraints
    from repro.core.presets import scnn_like, three_level_arch
    wl = matmul(3136, 576, 64, densities={"A": ("uniform", 0.4),
                                          "B": ("uniform", 0.55)})
    design = scnn_like(three_level_arch())
    cons = MapspaceConstraints(budget=POP * GENERATIONS, seed=0,
                               spatial={1: {"n": 8}})
    return design, wl, cons


def decoded_population(design, wl, cons, n: int, seed: int):
    import jax
    from repro.search.encoding import MapspaceEncoding
    from repro.search.strategies import init_population
    enc = MapspaceEncoding(wl, design.arch.num_levels, cons)
    pop = enc.repair(init_population(jax.random.PRNGKey(seed), enc, n))
    return enc, pop


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def phase_search(pop: int = POP, chunk: int = CHUNK,
                 generations: int = GENERATIONS) -> None:
    from repro.core import compile_stats
    from repro.core.engine import Sparseloop
    from repro.search import SearchConfig, run_search
    design, wl, cons = conv2_x()
    cfg = SearchConfig(fused_chunk=chunk)
    kw = dict(strategy="es", key=0, pop_size=pop, generations=generations,
              mesh=None, fused=True, config=cfg)
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="fused=True requested")
        with compile_stats.track() as st:
            res = run_search(design, wl, cons, **kw)
        rerun = run_search(design, wl, cons, **kw)
    check(st.compiles_by_kind.get("fused", 0) == 1,
          f"fused compiles {st.compiles_by_kind}, want exactly 1")
    check(st.scalar_evals == 0, f"{st.scalar_evals} scalar evals in search")
    check(res.best is not None, "no validated winner")
    ev = Sparseloop(design).evaluate(wl, res.best_nest)
    winner_rel = float(rel_dev(res.best.edp, ev.edp))
    device_rel = float(rel_dev(res.log.records[-1].best_edp, ev.edp))
    check(ev.result.valid and winner_rel <= PARITY_BOUND,
          f"winner vs fresh scalar oracle: rel {winner_rel}")
    check(device_rel <= PARITY_BOUND,
          f"device best EDP vs scalar winner: rel {device_rel}")
    same = rerun.log.to_json(timing=False) == res.log.to_json(timing=False)
    check(same, "same-key re-run diverged")
    report("search", t0, st, pop=pop, generations=generations,
           chunk=chunk, chunks=res.log.timing["chunks"],
           best_edp=ev.edp, winner_rel=winner_rel,
           device_best_rel=device_rel, rerun_identical=same)


def phase_engine(n: int = POP) -> None:
    import numpy as np
    from repro.core import compile_stats
    from repro.core.engine import Sparseloop
    design, wl, cons = conv2_x()
    enc, pop = decoded_population(design, wl, cons, n, seed=1)
    model = Sparseloop(design)
    t0 = time.perf_counter()
    with compile_stats.track() as st:
        bucket, bounds, ids = enc.decode_bucketed(pop)
        out = model.bucketed_model(wl, bucket).evaluate(bounds, ids)
    t_dev = time.perf_counter() - t0
    check(st.scalar_evals == 0, f"{st.scalar_evals} scalar evals")
    ref = {k: np.empty(n) for k in ("cycles", "energy_pj", "edp")}
    valid = np.empty(n, bool)
    for i, g in enumerate(pop):
        # the oracle zeroes the metrics of a mapping that overflows a
        # level, so metrics compare without the capacity check and
        # validity compares with it
        nest = enc.nest_of(g)
        ev = model.evaluate(wl, nest, check_capacity=False)
        ref["cycles"][i], ref["energy_pj"][i] = ev.cycles, ev.energy_pj
        ref["edp"][i] = ev.edp
        valid[i] = model.evaluate(wl, nest).result.valid
    worst = {k: float(rel_dev(out[k], ref[k]).max()) for k in ref}
    argworst = {k: int(rel_dev(out[k], ref[k]).argmax()) for k in ref}
    mism = int((np.asarray(out["valid"]) != valid).sum())
    check(all(w <= PARITY_BOUND for w in worst.values()),
          f"engine vs scalar oracle: worst rel {worst} at {argworst}")
    check(mism == 0, f"{mism} validity mismatches")
    report("engine", t0, st, candidates=n, device_s=t_dev,
           worst_rel=worst, worst_at=argworst, valid=int(valid.sum()),
           valid_mismatches=mism)


def phase_fleet(config: str = "qwen3-4b", reduced: bool = False,
                check_rows: int = 4) -> None:
    from repro.core.advisor import tpu_mapping
    from repro.core.engine import Sparseloop
    from repro.core.mapping import LoopNest
    from repro.core.workload import matmul
    from repro.fleet.sweep import default_options, fleet_sweep
    t0 = time.perf_counter()
    rep = fleet_sweep([config], reduced=reduced)
    st = rep.stats
    check(st["compiles"] <= rep.compile_bound,
          f"{st['compiles']} compiles > bound {rep.compile_bound}")
    check(st["scalar_evals"] == 0, f"{st['scalar_evals']} scalar evals")
    opts = {o.name: o for o in default_options()}
    # a few rows spread over phases and layers, every option of each.
    # The engine treats unit-bound loops as absent and the scalar oracle
    # does not, so the oracle gets the mapping without its unit loops;
    # the deviation on the mapping as written is printed beside it.
    step = max(1, len(rep.rows) // check_rows)
    worst = with_units = 0.0
    for row in rep.rows[::step][:check_rows]:
        for name, got in row.options.items():
            opt = opts[name]
            wl = matmul(row.M, row.K, row.N, densities=opt.densities)
            nest = tpu_mapping(row.M, row.K, row.N)
            bare = LoopNest(tuple(lp for lp in nest.loops if lp.bound > 1),
                            nest.num_levels)
            engine = Sparseloop(opt.design)
            ev = engine.evaluate(wl, bare, check_capacity=False)
            ev_u = engine.evaluate(wl, nest, check_capacity=False)
            for k in ("cycles", "energy_pj", "edp"):
                worst = max(worst, float(rel_dev(got[k], getattr(ev, k))))
                with_units = max(with_units,
                                 float(rel_dev(got[k], getattr(ev_u, k))))
    check(worst <= PARITY_BOUND, f"fleet rows vs scalar oracle: {worst}")
    phases = sorted({r.phase for r in rep.rows})
    print(f"[fleet] {rep.summary()}", flush=True)
    report("fleet", t0, None, config=config, reduced=reduced,
           phases=phases, options=list(rep.option_names),
           entries=rep.total_entries, unique_shapes=rep.unique_shapes,
           compiles=st["compiles"], compile_bound=rep.compile_bound,
           compile_s=rep.compile_seconds, warm_s=rep.eval_seconds,
           scalar_evals=st["scalar_evals"], rows_checked=check_rows,
           worst_rel=worst, worst_rel_with_unit_loops=with_units)


def phase_service(batch: int = SERVICE_BATCH, requests: int = 3,
                  timeout_s: float = 600.0) -> None:
    import numpy as np
    from repro.core import compile_stats
    from repro.core.engine import Sparseloop
    from repro.dse import EvaluationService
    design, wl, cons = conv2_x()
    enc, pop = decoded_population(design, wl, cons,
                                  2 * requests * batch, seed=2)
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = Sparseloop(design).bucketed_model(wl, bucket)
    results: dict = {}
    errors: list = []
    t0 = time.perf_counter()
    with compile_stats.track() as st:
        with EvaluationService(batch_slots=batch) as svc:
            def ask(ci: int) -> None:
                client = svc.client(f"client{ci}")
                try:
                    for r in range(requests):
                        lo = (ci * requests + r) * batch
                        sl = slice(lo, lo + batch)
                        results[lo] = client.evaluate(
                            bm, bounds[sl], rank_ids=ids[sl],
                            timeout=timeout_s)
                except Exception as e:  # noqa: BLE001 -- re-raised below
                    errors.append(e)

            threads = [threading.Thread(target=ask, args=(ci,))
                       for ci in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout_s * requests)
            check(not any(t.is_alive() for t in threads),
                  "a service request hung")
            svc_stats = svc.stats()
        if errors:
            raise errors[0]
        check(len(results) == 2 * requests,
              f"{len(results)} of {2 * requests} requests resolved")
        mism = 0
        for lo, res in results.items():
            sl = slice(lo, lo + batch)
            direct = bm.evaluate(bounds[sl], ids[sl])
            mism += sum(not np.array_equal(res[k], direct[k])
                        for k in direct)
    check(mism == 0, f"{mism} service results differ from direct evaluate")
    check(st.scalar_evals == 0, f"{st.scalar_evals} scalar evals")
    report("service", t0, st, clients=2, requests=2 * requests,
           candidates_per_request=batch, mismatches=mism,
           service=svc_stats)


def _kernel_case(name: str, fn, args, ref, tol: float,
                 interpret: bool) -> dict:
    """Compile one kernel call, check its HLO holds the TPU kernel, run
    it, and compare with its reference."""
    import jax
    import numpy as np
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    on_chip = "tpu_custom_call" in compiled.as_text()
    check(on_chip or interpret, f"{name}: no tpu_custom_call in its HLO")
    out = compiled(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    out = compiled(*args)
    out.block_until_ready()
    t_run = time.perf_counter() - t0
    want = np.asarray(ref(*args), np.float32)
    err = float(np.max(np.abs(np.asarray(out, np.float32) - want))
                / max(1e-9, float(np.max(np.abs(want)))))
    check(err <= tol, f"{name}: max error {err} / max |ref| > {tol}")
    return {"compile_s": t_compile, "run_s": t_run, "rel_err": err,
            "tpu_custom_call": on_chip}


def phase_kernels(mkn=KERNEL_MKN, flash=FLASH_SHAPE,
                  interpret: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.block_mm.ops import (block_indices, block_mm_ref,
                                            gated_mm, skip_mm)
    from repro.kernels.flash_attention.ops import (flash_attention,
                                                   flash_attention_ref)
    from repro.kernels.nm_spmm.ops import nm_spmm, nm_spmm_ref
    from repro.sparsity.nm import (nm_prune_dense, pack_nm, pack_offsets,
                                   unpack_offsets)
    M, K, N = mkn
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    a = jnp.asarray(rng.standard_normal((M, K)), bf)
    w = jnp.asarray(rng.standard_normal((K, N)), bf)
    t0 = time.perf_counter()
    rows = {}
    for n, m in ((2, 4), (2, 8)):
        vals, idx = pack_nm(nm_prune_dense(w, n, m), n, m)
        rows[f"nm_spmm {n}:{m}"] = _kernel_case(
            f"nm_spmm {n}:{m}",
            lambda a, v, i, n=n, m=m: nm_spmm(a, v, i, n=n, m=m,
                                              interpret=interpret),
            (a, vals, idx),
            lambda a, v, i, n=n, m=m: nm_spmm_ref(a, v, i, n, m),
            1e-3, interpret)
        packed = pack_offsets(idx, m)
        rows[f"nm_spmm {n}:{m} packed"] = _kernel_case(
            f"nm_spmm {n}:{m} packed",
            lambda a, v, p, n=n, m=m: nm_spmm(a, v, p, n=n, m=m,
                                              packed=True,
                                              interpret=interpret),
            (a, vals, packed),
            lambda a, v, p, n=n, m=m: nm_spmm_ref(
                a, v, unpack_offsets(p, m, v.shape[0]), n, m),
            1e-3, interpret)
    bs = 128
    mask = rng.random((K // bs, N // bs)) < 0.25
    mask[0, :] = True
    wm = (np.asarray(w, np.float32).reshape(K // bs, bs, N // bs, bs)
          * mask[:, None, :, None]).reshape(K, N)
    wm = jnp.asarray(wm, bf)
    ks, js = block_indices(mask)
    jmask = jnp.asarray(mask, jnp.int32)
    ref_blk = (lambda a, w, *_: block_mm_ref(a, w, jmask, bk=bs, bn=bs))
    rows["skip_mm"] = _kernel_case(
        "skip_mm", lambda a, w, ki, ji: skip_mm(
            a, w, ki, ji, bm=bs, bk=bs, bn=bs, interpret=interpret),
        (a, wm, jnp.asarray(ks), jnp.asarray(js)), ref_blk, 1e-3,
        interpret)
    rows["gated_mm"] = _kernel_case(
        "gated_mm", lambda a, w, mk: gated_mm(
            a, w, mk, bm=bs, bk=bs, bn=bs, interpret=interpret),
        (a, wm, jmask), ref_blk, 1e-3, interpret)
    B, S, H, D = flash
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), bf)
               for _ in range(3))

    def flash_ref(q, k, v):
        f = (lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, D))
        return flash_attention_ref(f(q), f(k), f(v)).reshape(
            B, H, S, D).transpose(0, 2, 1, 3)

    rows["flash_attention"] = _kernel_case(
        "flash_attention",
        lambda q, k, v: flash_attention(q, k, v, interpret=interpret),
        (q, k, v), flash_ref, 3e-2, interpret)
    report("kernels", t0, None, mkn=list(mkn), flash=list(flash),
           block_density=0.25, kernels=rows)


def phase_agree(interpret: bool = False, **kw) -> None:
    from repro.core import compile_stats
    from repro.fleet.validate import agreement_summary, validate_fleet
    t0 = time.perf_counter()
    with compile_stats.track() as st:
        rows = validate_fleet(interpret=interpret, **kw)
    for r in rows:
        print("[agree-row] " + json.dumps(r.as_dict()), flush=True)
    print(agreement_summary(rows), flush=True)
    bad = [f"{r.config}/{r.layer}/{r.arm}" for r in rows if not r.agree]
    # a sign disagreement on the chip is a finding to record, not a gate
    report("agree", t0, st, rows=len(rows), agree=len(rows) - len(bad),
           disagree=bad)


def phase_sharded(n_devices: int, pop: int = POP,
                  generations: int = 6) -> None:
    """Host-loop ES with the population sharded over every device,
    against the same key on one device."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import compile_stats
    from repro.core.engine import Sparseloop
    from repro.search import run_search
    from repro.search.runner import population_mesh
    design, wl, cons = conv2_x()
    mesh = population_mesh()
    check(mesh is not None and mesh.size == n_devices,
          f"population mesh {mesh} does not span {n_devices} devices")
    kw = dict(strategy="es", key=0, pop_size=pop, generations=generations,
              fused=False)
    t0 = time.perf_counter()
    with compile_stats.track() as st:
        sharded = run_search(design, wl, cons, mesh=mesh, **kw)
    t_sharded = time.perf_counter() - t0
    single = run_search(design, wl, cons, mesh=None, **kw)
    check(st.scalar_evals == 0, f"{st.scalar_evals} scalar evals")
    traj = {}
    for field in ("best_edp", "best_cycles", "best_energy_pj"):
        a = sharded.log.trajectory(field)
        b = single.log.trajectory(field)
        traj[field] = float(rel_dev(a, b).max())
    check(max(traj.values()) <= PARITY_BOUND,
          f"sharded vs single-device trajectories: {traj}")

    # where the candidate axis lands: the sharded program's outputs
    enc, popn = decoded_population(design, wl, cons, pop, seed=3)
    bucket, bounds, ids = enc.decode_bucketed(popn)
    bm = Sparseloop(design).bucketed_model(wl, bucket)
    storage, comp = bm._bind_arch(None, pop)
    with jax.enable_x64():
        out = bm._prog.sharded(mesh)(
            (jax.numpy.asarray(bounds, jax.numpy.float64),
             jax.numpy.asarray(ids, jax.numpy.int64),
             (jax.numpy.asarray(storage), jax.numpy.asarray(comp))),
            bm._bind_params(None))
        cyc = out["cycles"]
        cyc.block_until_ready()
    shards = {str(s.device): int(s.data.shape[0])
              for s in cyc.addressable_shards}
    check(cyc.sharding.spec == P(mesh.axis_names[0]),
          f"output sharding {cyc.sharding}")
    check(len(shards) == n_devices
          and set(shards.values()) == {pop // n_devices},
          f"candidate axis not spread over {n_devices} devices: {shards}")
    direct = bm.evaluate(bounds, ids, mesh=None)
    out_rel = float(rel_dev(np.asarray(cyc), direct["cycles"]).max())
    check(out_rel <= PARITY_BOUND, f"sharded vs single outputs: {out_rel}")
    mem = {}
    for d in jax.devices():
        ms = d.memory_stats() or {}
        mem[str(d)] = ms.get("peak_bytes_in_use")
    check(all(v for v in mem.values()),
          f"a device held nothing: peak bytes {mem}")
    report("sharded", t0, st, devices=n_devices, pop=pop,
           generations=generations, sharded_s=t_sharded,
           trajectory_rel=traj, output_shards=shards,
           output_rel=out_rel, peak_bytes=mem,
           fused_scan="runs on one device whatever mesh is passed")


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[device] {json.dumps(dev)}", flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']}",
              file=sys.stderr)
        return 2
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} devices", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[cache] dir={enable_compile_cache()}", flush=True)

    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_search()
        phase_engine()
        phase_fleet()
        phase_service()
        phase_kernels()
        phase_agree()
    print(f"[cache] {json.dumps(cache)}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
