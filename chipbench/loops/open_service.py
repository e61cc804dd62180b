"""Open loop into one ``EvaluationService``: requests arrive on a fixed
schedule, whatever the service's progress, from several tenants.

Mix keys: ``rate_per_s`` (mean arrival rate), ``burst`` (``factor``,
``seconds`` and ``period_s``: the rate is ``factor`` times the mean for
``seconds`` in every ``period_s``), ``tenants`` and ``tenant_zipf_s``,
``layer_zipf_s`` (each request is for one layer of the configuration),
``sizes`` and ``size_weights`` (candidates per request), ``batch_slots``,
``pool`` (candidates decoded per layer in set-up), ``sample_requests``
and ``sample_rows`` (how many answers the check draws from the seed) and
``limits``.

Every seed gets the same multiset of request sizes, layers, tenants and
inter-arrival gaps (exponential quantiles, so the count of requests in
the window is fixed), shuffled by the seed; each request takes a
contiguous slice of its layer's pool at an offset drawn from the seed.
A request's latency runs from its due time to its result on the client.

Answers checked once the window has closed: requests drawn from the
seed against a direct ``BucketedModel.evaluate`` of their own rows (in
the service's slot shape, exact), and rows drawn from the seed against
the reference (cycles, energy and EDP without the capacity check,
validity with it).
"""
from __future__ import annotations

import queue
import threading
import time
import types

import numpy as np

from chipbench import common, oracle

#: threads that wait on responses and stamp their arrival on the client
WAITERS = 64
#: how long past the close a response may still come
GRACE_S = 60.0


def _shares(n: int, weights) -> list[int]:
    """``n`` split in proportion to ``weights``, summing to ``n``."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(raw - out)[::-1][: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def _zipf(k: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, k + 1, dtype=np.float64) ** s


def _rate_at(mix, t: float) -> float:
    b = mix["burst"]
    rate = float(mix["rate_per_s"])
    return rate * b["factor"] if (t % b["period_s"]) < b["seconds"] else rate


def schedule(mix: dict, seconds: float, seed: int, layers: int) -> list:
    """The window's requests: (due_s, layer, tenant, size), by due time.

    Arrivals are a Poisson process whose rate follows the burst pattern:
    unit-rate arrival gaps, as the exponential distribution's quantiles,
    are mapped through the cumulative rate."""
    dt = 1e-3
    grid = np.arange(0.0, seconds + dt, dt)
    cum = np.concatenate([[0.0], np.cumsum(
        [_rate_at(mix, t) * dt for t in grid[:-1]])])
    n = int(round(cum[-1]))
    rng = common.rng(seed, 4)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= cum[-1] / gaps.sum()
    rng.shuffle(gaps)
    due = np.interp(np.cumsum(gaps) - gaps / 2, cum, grid)
    sizes = np.repeat(mix["sizes"], _shares(n, mix["size_weights"]))
    which = np.repeat(np.arange(layers),
                      _shares(n, _zipf(layers, mix["layer_zipf_s"])))
    tenant = np.repeat(np.arange(mix["tenants"]),
                       _shares(n, _zipf(mix["tenants"],
                                        mix["tenant_zipf_s"])))
    for a in (sizes, which, tenant):
        rng.shuffle(a)
    return [(float(d), int(w), int(t), int(s))
            for d, w, t, s in zip(due, which, tenant, sizes)]


def setup(ctx):
    import jax
    from repro import obs
    from repro.core.engine import Sparseloop
    from repro.dse import EvaluationService
    from repro.search.encoding import MapspaceEncoding
    from repro.search.strategies import init_population
    cfg, mix = ctx.cfg, ctx.mix
    design = common.program_design(cfg["design"])
    engine = Sparseloop(design)
    pool = int(mix["pool"])
    layers = []
    for i, lay in enumerate(cfg["layers"]):
        wl = common.program_workload(lay)
        enc = MapspaceEncoding(wl, design.arch.num_levels,
                               common.constraints(cfg, pool))
        genomes = enc.repair(init_population(
            jax.random.PRNGKey(common.derive(ctx.seed, 5, i)), enc, pool))
        bucket, bounds, ids = enc.decode_bucketed(genomes)
        layers.append(types.SimpleNamespace(
            spec=lay, enc=enc, genomes=np.asarray(genomes),
            bounds=np.asarray(bounds), ids=np.asarray(ids),
            model=engine.bucketed_model(wl, bucket)))
    if ctx.tracing:
        obs.enable()
    svc = EvaluationService(batch_slots=int(mix["batch_slots"]))
    st = types.SimpleNamespace(ctx=ctx, layers=layers, svc=svc, obs=obs,
                               slots=int(mix["batch_slots"]))
    # every request pads or splits to the one slot shape: one request
    # per layer warms every program the window runs
    for lay in layers:
        svc.submit(lay.model, lay.bounds[:1], lay.ids[:1]).result(
            timeout=1200)
    return st


def _rows(lay, offset: int, size: int) -> np.ndarray:
    return (offset + np.arange(size)) % len(lay.bounds)


def window(st, seconds: float) -> dict:
    mix = st.ctx.mix
    plan = schedule(mix, seconds, st.ctx.seed, len(st.layers))
    rng = common.rng(st.ctx.seed, 6)
    offsets = rng.integers(0, int(mix["pool"]), size=len(plan))
    done = [None] * len(plan)
    results = [None] * len(plan)
    todo: queue.Queue = queue.Queue()

    def waiter():
        while True:
            item = todo.get()
            if item is None:
                return
            i, fut = item
            try:
                results[i] = fut.result(timeout=seconds + GRACE_S)
                done[i] = time.perf_counter()
            except Exception as e:  # noqa: BLE001 -- counted as failed
                results[i] = e

    threads = [threading.Thread(target=waiter, daemon=True)
               for _ in range(WAITERS)]
    for t in threads:
        t.start()
    late = []
    tracer = st.obs.tracer()
    t0 = time.perf_counter()
    for i, (due, which, tenant, size) in enumerate(plan):
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - (t0 + due))
        lay = st.layers[which]
        rows = _rows(lay, int(offsets[i]), size)
        fut = st.svc.submit(lay.model, lay.bounds[rows], lay.ids[rows],
                            client=f"tenant{tenant}")
        todo.put((i, fut))
        st.ctx.tick()
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join(seconds + GRACE_S)
    window_s = time.perf_counter() - t0
    lat = [done[i] - (t0 + plan[i][0]) for i in range(len(plan))
           if done[i] is not None]
    failed = sum(d is None for d in done)
    records = {"plan": plan, "offsets": offsets, "results": results,
               "latency_s": lat, "window_s": window_s,
               "attempted": len(plan), "failed": failed,
               "completed": len(plan) - failed,
               "notes": {"late_p95_ms": 1e3 * common.percentile(late, 95),
                         "late_max_ms": 1e3 * max(late, default=0.0),
                         "requests": len(plan)}}
    if tracer is not None:
        epoch = tracer.epoch
        records["spans"] = [s for s in tracer.spans
                            if s.t_start + epoch >= t0]
    return records


def end_to_end(records) -> dict:
    lat = records["latency_s"]
    return {"req_p95_ms": 1e3 * common.percentile(lat, 95)
            if lat else float("inf")}


def modelled(st, records) -> list[str]:
    best: dict = {}
    for (_due, which, _t, _s), res in zip(records["plan"],
                                          records["results"]):
        if isinstance(res, dict):
            edp = np.where(res["valid"], res["edp"], np.inf).min()
            name = st.layers[which].spec["name"]
            best[name] = min(best.get(name, np.inf), float(edp))
    return [f"[modelled] best_edp {n} {v!r}" for n, v in sorted(best.items())]


def _direct(st, lay, rows) -> dict:
    """The program's own evaluation of rows, in the service's slot shape
    (padding repeats the last row, as the service does)."""
    parts = []
    for start in range(0, len(rows), st.slots):
        r = rows[start:start + st.slots]
        pad = np.concatenate([r, np.repeat(r[-1:], st.slots - len(r))])
        out = lay.model.evaluate(lay.bounds[pad], lay.ids[pad])
        parts.append({k: np.asarray(v)[:len(r)] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def check(st, records, ctx) -> list:
    mix = ctx.mix
    st.svc.close()
    plan, results = records["plan"], records["results"]
    answered = [i for i, r in enumerate(results) if isinstance(r, dict)]
    rng = common.rng(ctx.seed, 7)
    mism = 0
    for i in rng.choice(answered, size=min(len(answered),
                                           int(mix["sample_requests"])),
                        replace=False):
        _due, which, _t, size = plan[int(i)]
        lay = st.layers[which]
        got = results[int(i)]
        want = _direct(st, lay, _rows(lay, int(records["offsets"][i]), size))
        for k, v in want.items():
            g = np.asarray(got.get(k, []))
            mism += (len(v) if g.shape != v.shape
                     else int((g != v).reshape(len(v), -1).any(1).sum()))
    rel, valid_wrong = [], 0
    for i in rng.choice(answered, size=min(len(answered),
                                           int(mix["sample_rows"]))):
        _due, which, _t, size = plan[int(i)]
        lay = st.layers[which]
        j = int(rng.integers(0, size))
        row = _rows(lay, int(records["offsets"][i]), size)[j]
        nest = lay.enc.nest_of(lay.genomes[row])
        loops = oracle.ref_nest(nest.loops, nest.num_levels)
        spec = lay.spec

        def workload(spec=spec):
            return oracle.ref_workload(
                spec, spec.get("densities"))

        design = ctx.cfg["design"]
        ref = oracle.evaluate(design, workload, loops, False)
        got = results[int(i)]
        if len(got["cycles"]) != size:
            rel.append(np.inf)
            continue
        got = {k: got[k][j] for k in (*oracle.STATS, "valid")}
        if ctx.control:
            got = oracle.evaluate(design, workload, loops, True)
        valid = oracle.evaluate(design, workload, loops, False,
                                check_capacity=True)["valid"]
        valid_wrong += bool(got["valid"]) != valid
        rel += [oracle.rel_dev(got[k], ref[k]) for k in oracle.STATS]
    lim = mix["limits"]
    return [oracle.Check("direct_mismatch", mism, 0),
            oracle.Check("row_rel", oracle.worst(rel), lim["row_rel"]),
            oracle.Check("valid_mismatch", valid_wrong, 0)]
