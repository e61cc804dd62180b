"""Closed loop of mapspace searches: one caller, back-to-back
``run_search`` calls over the configuration's layers, round-robin.

Mix keys: ``strategy``, ``pop_size``, ``generations``, ``fused``,
``layers`` (names, default all), and ``limits``.  Each search's key is
derived from ``--seed`` and its index in the window; the layer sequence
is the same for every seed.  The searches run on one device.

Answers checked once the window has closed, for every search of the
window:

* the device-side best (cycles, energy, EDP of the last generation's
  best-so-far) and the host oracle's validated winner, each against the
  reference's evaluation of the winning mapping;
* that each search ran its generations over its whole population: the
  log holds ``generations`` records and ``pop_size x generations``
  evaluations (exact);
* that the searches advance: the share of searches whose best-so-far
  after the last generation is no better than after the first;
* that the whole population is evaluated: the share of the window's
  candidates that the device counted invalid.  A population that is
  half left out reads about twice a sound run's share.
"""
from __future__ import annotations

import gc
import time
import types
import warnings

from chipbench import common, oracle


def setup(ctx):
    from repro.search import run_search
    cfg, mix = ctx.cfg, ctx.mix
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
                generations=int(mix["generations"]), mesh=None,
                fused=bool(mix["fused"])))
    # one search per layer warms its programs and the oracle walk
    for i in range(len(layers)):
        _search(st, i, key=common.derive(ctx.seed, 1, i))
    return st


def _search(st, i: int, key: int):
    layer = i % len(st.layers)
    with warnings.catch_warnings():
        # a search that silently left the fused path is not this cell
        warnings.filterwarnings("error", message="fused=True requested")
        with common.annotate("bench.search", layer=layer):
            return st.run_search(st.design, st.workloads[layer], st.cons,
                                 key=key, **st.kw)


def _loop_seconds(log) -> float:
    """The search loop's own seconds: the fused chunks' wall, or the
    host loop's per-generation wall."""
    chunks = log.timing.get("chunks")
    if chunks:
        return float(sum(c["wall_s"] for c in chunks))
    return float(sum(r.wall_time_s or 0.0 for r in log.records))


class _HostWatch:
    """Seconds the process spent in Python's garbage collector and in
    JAX's tracing and compiling, summed since the watch began: where a
    search that took far longer than the rest spent its time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.gc_s = self.jax_s = 0.0
        self._gc_t = 0.0

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t

    def _jax(self, event, duration, **kw):
        if event in self.EVENTS:
            self.jax_s += duration

    def __enter__(self):
        import jax
        gc.callbacks.append(self._gc)
        jax.monitoring.register_event_duration_secs_listener(self._jax)
        return self

    def __exit__(self, *exc):
        import jax
        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._jax)

    def now(self) -> tuple:
        return (time.perf_counter(), time.process_time(), self.gc_s,
                self.jax_s)


def window(st, seconds: float) -> dict:
    searches = []
    t0 = time.perf_counter()
    i = 0
    with _HostWatch() as watch:
        while True:
            before = watch.now()
            res = _search(st, i, key=common.derive(st.ctx.seed, 2, i))
            wall, cpu, gc_s, jax_s = (b - a for a, b in
                                      zip(before, watch.now()))
            searches.append({"index": i, "layer": i % len(st.layers),
                             "wall_s": wall, "cpu_s": cpu, "gc_s": gc_s,
                             "jax_s": jax_s,
                             "loop_s": _loop_seconds(res.log),
                             "generations": len(res.log.records),
                             "result": res})
            st.ctx.tick()
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        gc_total, jax_total = watch.gc_s, watch.jax_s
    failed = sum(s["result"].best is None for s in searches)
    walls = [s["wall_s"] for s in searches]
    loop_s = sum(s["loop_s"] for s in searches)
    med = common.percentile(walls, 50)
    worst = max(searches, key=lambda s: s["wall_s"])
    # where the window's time went, and where the slowest search spent
    # its own (its process CPU seconds tell a stall of the process from
    # one of the machine): printed, not a metric
    notes = {"loop_s": loop_s, "rest_s": sum(walls) - loop_s,
             "gc_s": gc_total, "jax_compile_s": jax_total,
             "wall_p50_ms": 1e3 * med,
             "wall_p99_ms": 1e3 * common.percentile(walls, 99),
             "slow": sum(w > 2 * med for w in walls),
             "worst": {"index": worst["index"], "at_s": sum(
                           s["wall_s"] for s in searches[:worst["index"]]),
                       **{k: 1e3 * worst[k] for k in
                          ("wall_s", "loop_s", "cpu_s", "gc_s", "jax_s")}}}
    return {"searches": searches, "window_s": window_s,
            "attempted": len(searches), "failed": failed,
            "completed": len(searches) - failed, "notes": notes}


def end_to_end(records) -> dict:
    return {"search_s": records["window_s"] / max(1, records["completed"])}


def modelled(st, records) -> list[str]:
    best: dict = {}
    for s in records["searches"]:
        res = s["result"]
        if res.best is None:
            continue
        name = st.layers[s["layer"]]["name"]
        best[name] = min(best.get(name, float("inf")), res.best.edp)
    return [f"[modelled] best_edp {name} {edp!r}"
            for name, edp in sorted(best.items())]


def _reference(ctx, layer: dict, loops, control: bool) -> dict:
    """The reference's statistics and capacity verdict of one mapping
    (searches often end on the same winner, so each is evaluated once)."""
    key = (layer["name"], loops, control)
    if key not in _REFERENCE:
        def workload():
            return oracle.ref_workload(layer, layer.get("densities"))

        out = oracle.evaluate(ctx.cfg["design"], workload, loops, control)
        out["valid"] = oracle.evaluate(ctx.cfg["design"], workload, loops,
                                       control, check_capacity=True)["valid"]
        _REFERENCE[key] = out
    return _REFERENCE[key]


_REFERENCE: dict = {}


def check(st, records, ctx) -> list:
    pop, gens = int(ctx.mix["pop_size"]), int(ctx.mix["generations"])
    dev_rel, host_rel = [], []
    invalid = short = stalled = 0
    evaluated = counted_valid = 0
    for s in records["searches"]:
        res = s["result"]
        log = res.log.records
        short += (len(log) != gens
                  or res.log.evaluations != pop * gens)
        if log:
            stalled += not log[-1].best_fitness < log[0].best_fitness
            evaluated += log[-1].evaluations
            counted_valid += log[-1].valid
        if res.best is None:
            continue
        layer = st.layers[s["layer"]]
        loops = oracle.ref_nest(res.best_nest.loops,
                                res.best_nest.num_levels)
        ref = _reference(ctx, layer, loops, False)
        invalid += not ref["valid"]
        last = log[-1]
        device = {"cycles": last.best_cycles,
                  "energy_pj": last.best_energy_pj, "edp": last.best_edp}
        host = {k: getattr(res.best, k) for k in oracle.STATS}
        if ctx.control:
            device = host = _reference(ctx, layer, loops, True)
        for k in oracle.STATS:
            dev_rel.append(oracle.rel_dev(device[k], ref[k]))
            host_rel.append(oracle.rel_dev(host[k], ref[k]))
    n = max(1, len(records["searches"]))
    lim = ctx.mix["limits"]
    return [
        oracle.Check("device_best_rel", oracle.worst(dev_rel),
                     lim["device_best_rel"]),
        oracle.Check("winner_rel", oracle.worst(host_rel),
                     lim["winner_rel"]),
        oracle.Check("winner_invalid", invalid, 0),
        oracle.Check("searches_short", short, 0),
        oracle.Check("stalled_share", stalled / n, lim["stalled_share"]),
        oracle.Check("invalid_share",
                     1.0 - counted_valid / max(1, evaluated),
                     lim["invalid_share"]),
    ]
