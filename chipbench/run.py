"""Run one cell of the on-chip benchmark and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it is found by name:

* its configuration, ``chipbench/configs/<config>.json`` (sizes, design,
  source);
* its traffic mix, ``chipbench/traffic/<traffic>.json``, whose ``loop``
  names the generator in ``chipbench/loops/<loop>.py`` that reads it;
* each per-layer metric that lists the cell, ``chipbench/metrics/<metric>.py``,
  a reader of the run's records.

One run builds the cell's inputs from ``--seed``, warms every program the
window uses (set-up, ``setup_s``), measures for ``--seconds``, then checks
what the window produced against the plain reference
(``chipbench/reference``) and prints, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``breakdown``) and, last, ``checks``:
each compared number beside its limit.  With ``--trace 0`` the metrics are
the cell's end-to-end metrics; with ``--trace 1`` the per-layer ones, and
a profiler trace of the window's first ten seconds gives the device's
busy time.

It refuses to run without a TPU (or with fewer chips than the cell asks
for).  ``--rehearse`` runs the same code on the CPU at the mix's small
``rehearse`` sizes and prints counts and checks only, never a device
metric.  ``--control`` puts the reference computed in float32 in the
program's place in the comparison, which has to come out not correct.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT)]

from chipbench import common, oracle  # noqa: E402

#: the benchmark's definition: cells, metrics, bounds
SPEC = ROOT / "BENCHMARK.json"
#: where JAX's persistent compilation cache lives: fixed, in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a ``--trace 1`` run writes its profile before reducing it
TRACE_DIR = ROOT / ".chipbench_trace"
#: how much of the window, from its start, a ``--trace 1`` run profiles
TRACE_SECONDS = 10.0


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown cell)."""


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    """Import one file of the benchmark by path (metric files carry dots
    in their names, so they are not importable as modules)."""
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's entry, its configuration, its mix and its metrics, all
    resolved by name from ``BENCHMARK.json``."""
    if not SPEC.is_file():
        raise BenchError("BENCHMARK.json is missing")
    spec = json.loads(SPEC.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"cell": cell, "cfg": cfg, "mix": mix,
            "end_to_end": end_to_end, "per_layer": per_layer}


def clean_environment() -> None:
    """No toggle of the program steers the measured path, and the compile
    cache is the benchmark's own."""
    for k in list(os.environ):
        if k.startswith("REPRO_SEARCH_") or k == "REPRO_TRACE":
            del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def import_program():
    """The system under test, from this checkout's ``src`` only."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro
    except ImportError as e:
        raise BenchError(f"the program is missing: {e}") from None
    where = pathlib.Path(list(repro.__path__)[0]).resolve()
    if where != ROOT / "src" / "repro":
        raise BenchError(f"repro imported from {where}, not this checkout")
    return repro


def device_info(chips: int, rehearse: bool) -> dict:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        if info["platform"] != "cpu":
            raise BenchError("--rehearse runs on the CPU only")
        return info
    if info["platform"] != "tpu":
        raise BenchError(f"needs a TPU, JAX found {info['platform']}")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{info['count']}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def read_trace(window_s: float) -> dict:
    from chipbench import trace_reduce
    paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not paths:
        raise BenchError("the profiler wrote no trace")
    t0 = time.perf_counter()
    trace = trace_reduce.load(str(paths[-1]))
    red = trace_reduce.reduce(trace)
    print(f"[trace] read in {time.perf_counter() - t0!r} s, "
          f"{paths[-1].stat().st_size} bytes, planes "
          f"{json.dumps(trace['planes'])}", flush=True)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if red["window_s"] <= 0:
        red["window_s"] = window_s
    return red


class TracedSlice:
    """The profiler over the first :data:`TRACE_SECONDS` of the window
    (to the end of the first call that ends past them), inside the
    ``bench.window`` span that the trace reduction takes as the window.
    A fixed slice keeps the trace's size, and the time to read it, the
    same at any window length.  The loops call :meth:`tick` between
    calls."""

    def __init__(self, on: bool):
        self.on = on
        self.span = None
        self.t0 = 0.0

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.span = common.annotate("bench.window")
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def tick(self) -> None:
        if (self.span is not None
                and time.perf_counter() - self.t0 >= TRACE_SECONDS):
            self.stop()

    def stop(self) -> None:
        if self.span is None:
            return
        import jax
        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()


def run(args) -> tuple[dict, list]:
    """One run; returns the result line and the checks."""
    found = load_cell(args.workload)
    cell, mix = found["cell"], dict(found["mix"])
    if args.rehearse:
        mix.update(mix.get("rehearse", {}))
    clean_environment()
    import_program()
    device = device_info(int(cell["chips"]), args.rehearse)
    import jax
    from repro.compile_cache import enable_compile_cache
    if not args.rehearse:
        CACHE_DIR.mkdir(exist_ok=True)
        enable_compile_cache()
        # no size bound, so no eviction bookkeeping: an entry without its
        # access-time file would otherwise fail every later write
        jax.config.update("jax_compilation_cache_max_size", -1)
    loop = load_module(BENCH / "loops" / f"{mix['loop']}.py",
                       f"chipbench_loop_{mix['loop']}")
    ctx = types.SimpleNamespace(
        cfg=found["cfg"], mix=mix, seed=int(args.seed),
        chips=int(cell["chips"]), rehearse=args.rehearse,
        control=args.control, tracing=bool(args.trace))
    traced = TracedSlice(bool(args.trace))
    ctx.tick = traced.tick

    state = loop.setup(ctx)
    from repro.core import compile_stats
    setup_s = time.perf_counter() - T_PROCESS
    traced.start()
    with compile_stats.track() as st:
        records = loop.window(state, float(args.seconds))
    traced.stop()
    records["window_compiles"] = st.compiles
    if not args.rehearse:
        device["memory_peak_bytes"] = memory_peak_bytes()
    for line in loop.modelled(state, records):
        print(line, flush=True)
    print(f"[window] compiles={st.compiles} "
          f"attempted={records['attempted']} failed={records['failed']} "
          + " ".join(f"{k}={v!r}" for k, v in records.get("notes",
                                                            {}).items()),
          flush=True)

    checks = loop.check(state, records, ctx)
    correct = all(c.ok for c in checks) and records["failed"] == 0
    line = {"correct": bool(correct), "attempted": records["attempted"],
            "failed": records["failed"]}
    if args.trace:
        red = read_trace(records["window_s"])
        records["trace"] = red
        metrics = {}
        for m in found["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "chipbench_metric_" + m["name"].replace(
                                     ".", "_"))
            value = reader.read(records)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if not args.rehearse:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    else:
        values = loop.end_to_end(records)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in found["end_to_end"]}
    if args.rehearse:
        # a CPU run never carries a device metric's name
        line["rehearsal"] = {"counts": {k: v for k, v in records.items()
                                        if isinstance(v, (int, float))},
                             "metrics_read": len(metrics)}
    else:
        line["metrics"] = metrics
        line["device"] = device
    return line, checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU at the mix's rehearsal sizes")
    p.add_argument("--control", action="store_true",
                   help="compare the float32 reference in the program's "
                        "place (must come out not correct)")
    args = p.parse_args(argv)
    try:
        line, checks = run(args)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    line["checks"] = oracle.as_json(checks)
    for c in checks:
        print(c.describe(), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
