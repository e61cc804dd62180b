"""Benchmark aggregator: one module per paper table/figure (+ the
beyond-paper benches).  Prints a final ``name,us_per_call,derived`` CSV
and writes the same rows to ``BENCH_results.json`` (uploaded as a CI
artifact by the bench-smoke job so the perf trajectory is tracked
per-PR).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig1 stc   # substring filter
  PYTHONPATH=src python -m benchmarks.run --trace out.json fleet
                                   # + Perfetto trace of the run

Every row carries ``elapsed_s`` — the wall-clock its bench module took
— and ``--trace PATH`` switches the flight recorder on for the run and
writes a Chrome-trace/Perfetto ``trace.json`` (spans for every bench
module plus the engine's compile/eval spans) at PATH.

Regression gate (CI)
--------------------
``python -m benchmarks.run --gate [fresh] [baseline]`` compares a fresh
``BENCH_results.json`` against the committed baseline
(``benchmarks/baseline.json``) WITHOUT re-running anything, and exits
nonzero when any shared CPHC-family metric regressed by more than
``GATE_TOLERANCE`` (25%) — so a perf regression fails the bench-smoke
job outright instead of only tripping the job timeout.  Only rows (and
keys) present in BOTH files are compared, so running a bench subset
gates just that subset.

Gate suite (CI)
---------------
``python -m benchmarks.run --gate-suite [filters...]`` runs every CI
gate from the ``benchmarks/gates.py`` manifest in order with the CI
timeouts — the bench-smoke job is exactly install + this + artifact
upload, so the full gate sequence is reproducible locally.

Refreshing the baseline
-----------------------
``python -m benchmarks.run --update-baseline [filters...]`` runs the
benches (all of them, or a filtered subset) and regenerates
``benchmarks/baseline.json`` from the fresh rows: a full run replaces
the file, a filtered run merges by row name so the untouched rows keep
their committed values.  Rows carry their bench-module provenance, and
the merge PRUNES stale rows — a row whose module was removed from the
registry, or whose module just re-ran without re-emitting it (renamed
benchmark) — instead of silently keeping them forever and weakening the
``--gate`` comparison.  Benches that fail abort the update — a broken
bench must never overwrite a good baseline.
"""
from __future__ import annotations

import json
import os
import sys
import traceback
import warnings

RESULTS_JSON = "BENCH_results.json"
BASELINE_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")
#: relative drop in a CPHC-family metric that fails the gate
GATE_TOLERANCE = 0.25


def _parse_derived(derived: str) -> dict[str, float]:
    """Numeric ``key=value`` pairs out of a derived string ("cphc=825;
    speedup=87x" -> {"cphc": 825.0, "speedup": 87.0})."""
    out: dict[str, float] = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k.strip()] = float(v.strip().rstrip("x"))
        except ValueError:
            continue
    return out


def check_regression(fresh_rows: list[dict], baseline_rows: list[dict],
                     tolerance: float = GATE_TOLERANCE) -> list[str]:
    """Failure messages for every CPHC-family metric shared between the
    fresh rows and the baseline that dropped by more than ``tolerance``
    relative — *after common-mode correction*.  CPHC is inverse
    wall-clock, so a uniformly slower CI runner shifts every metric by
    the same factor; each ratio is therefore normalized by the median
    ratio across all compared metrics (capped at 1.0 so a uniformly
    *faster* runner can't mask a real regression).  A single code path
    regressing shows up as an outlier against the common mode and still
    fails.  Raises via the caller when ZERO metrics are comparable —
    a renamed bench row must not silently disable the gate."""
    base = {r["name"]: _parse_derived(r.get("derived", ""))
            for r in baseline_rows}
    ratios: list[tuple[str, str, float, float, float]] = []
    for row in fresh_rows:
        ref = base.get(row["name"])
        if ref is None:
            continue
        fresh = _parse_derived(row.get("derived", ""))
        for key, ref_val in ref.items():
            if not key.startswith("cphc") or key not in fresh:
                continue
            if ref_val <= 0:
                continue
            ratios.append((row["name"], key, fresh[key], ref_val,
                           fresh[key] / ref_val))
    if not ratios:
        return ["no CPHC metrics shared between fresh results and the "
                "baseline — the gate compared nothing (renamed bench "
                "row? wrong bench subset?); refresh "
                "benchmarks/baseline.json"]
    ordered = sorted(r[-1] for r in ratios)
    common_mode = min(1.0, ordered[len(ordered) // 2])
    failures: list[str] = []
    for name, key, fresh_val, ref_val, ratio in ratios:
        corrected = ratio / common_mode
        mark = "FAIL" if corrected < 1.0 - tolerance else "ok"
        print(f"  [{mark}] {name}:{key}  baseline={ref_val:.0f}  "
              f"fresh={fresh_val:.0f}  ({ratio:.2f}x raw, "
              f"{corrected:.2f}x vs common mode)")
        if corrected < 1.0 - tolerance:
            failures.append(
                f"{name}:{key} regressed to {corrected:.2f}x of baseline "
                f"after common-mode correction ({fresh_val:.0f} vs "
                f"{ref_val:.0f}, runner common mode {common_mode:.2f}x, "
                f"tolerance {1.0 - tolerance:.2f}x)")
    print(f"regression gate: {len(ratios)} CPHC metric(s) compared "
          f"(common mode {common_mode:.2f}x), {len(failures)} "
          f"regression(s)")
    return failures


def gate(argv: list[str]) -> None:
    fresh_path = argv[0] if argv else RESULTS_JSON
    baseline_path = argv[1] if len(argv) > 1 else BASELINE_JSON
    with open(fresh_path) as f:
        fresh = json.load(f)
    with open(baseline_path) as f:
        baseline = json.load(f)
    print(f"comparing {fresh_path} against {baseline_path} "
          f"(>{GATE_TOLERANCE:.0%} CPHC regression fails)")
    failures = check_regression(fresh, baseline)
    if failures:
        raise SystemExit("bench regression gate FAILED:\n  "
                         + "\n  ".join(failures))
    print("bench regression gate passed")


def registry() -> list[tuple[str, object]]:
    """The bench-module registry (name, module) — the single source of
    truth for which benchmarks exist; baseline rows record these names
    as provenance so ``--update-baseline`` can prune stale rows."""
    from . import (bench_bucketed_sweep, bench_codesign,
                   bench_fig1_formats, bench_fig11_scnn,
                   bench_fig12_eyerissv2, bench_fig13_dstc,
                   bench_fig15_16_stc_study, bench_fig17_codesign,
                   bench_fleet, bench_fused, bench_kernels, bench_obs,
                   bench_search_convergence, bench_service,
                   bench_stc_exact, bench_table5_cphc,
                   bench_table7_compression, bench_topology,
                   bench_vmapper)

    return [
        ("fig1_formats", bench_fig1_formats),
        ("table5_cphc", bench_table5_cphc),
        ("fig11_scnn", bench_fig11_scnn),
        ("fig12_eyerissv2", bench_fig12_eyerissv2),
        ("fig13_dstc", bench_fig13_dstc),
        ("table7_compression", bench_table7_compression),
        ("stc_exact", bench_stc_exact),
        ("fig15_16_stc_study", bench_fig15_16_stc_study),
        ("fig17_codesign", bench_fig17_codesign),
        ("vmapper", bench_vmapper),
        ("search_convergence", bench_search_convergence),
        ("bucketed_sweep", bench_bucketed_sweep),
        ("codesign_search", bench_codesign),
        ("kernels", bench_kernels),
        ("fleet", bench_fleet),
        ("obs", bench_obs),
        ("dse_service", bench_service),
        ("fused_search", bench_fused),
        ("topology_cosearch", bench_topology),
    ]


def run_benches(filters: list[str]
                ) -> tuple[list[dict], list[str]]:
    """Run the (filtered) bench modules; returns (row_dicts,
    failed_names) and writes ``BENCH_results.json``.  Each row dict
    carries ``module`` provenance (which registry entry emitted it) and
    ``elapsed_s`` — its module's wall-clock — so the modeling-speed
    story is itself a measured, archived artifact."""
    import time

    from repro import obs

    from .common import emit

    rows: list[dict] = []
    failed = []
    for name, mod in registry():
        if filters and not any(f in name for f in filters):
            continue
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        t0 = time.perf_counter()
        with obs.span(f"bench.{name}"):
            try:
                mod_rows = mod.run()
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failed.append(name)
                mod_rows = [(name, -1.0, f"FAILED:{type(e).__name__}")]
        elapsed = time.perf_counter() - t0
        rows.extend({"name": rname, "us_per_call": us,
                     "derived": derived, "module": name,
                     "elapsed_s": round(elapsed, 3)}
                    for rname, us, derived in mod_rows)
    print(f"\n{'=' * 72}\n== CSV (name,us_per_call,derived)\n{'=' * 72}")
    emit([(r["name"], r["us_per_call"], r["derived"]) for r in rows])
    with open(RESULTS_JSON, "w") as f:
        json.dump(rows, f, indent=2)
        f.write("\n")
    print(f"wrote {RESULTS_JSON} ({len(rows)} rows)")
    return rows, failed


def merge_baseline(baseline: list[dict], fresh: list[dict],
                   ran_modules: set[str],
                   known_modules: set[str]) -> list[dict]:
    """Merge fresh rows from a filtered run into the committed baseline,
    PRUNING stale rows instead of keeping them forever:

      * a baseline row whose ``module`` is no longer in the registry
        (benchmark removed/renamed) is dropped with a warning;
      * a baseline row whose module DID run this time but did not
        re-emit the row (bench row renamed) is dropped with a warning;
      * legacy rows without provenance are kept only while no fresh row
        replaces them, with a warning to regenerate the full baseline.

    Without pruning, renamed/removed rows linger in ``baseline.json``
    and the ``--gate`` step silently compares nothing for them."""
    fresh_names = {r["name"] for r in fresh}
    kept: list[dict] = []
    for row in baseline:
        module = row.get("module")
        if row["name"] in fresh_names:
            continue                       # replaced by a fresh row
        if module is None:
            warnings.warn(
                f"baseline row {row['name']!r} has no bench-module "
                f"provenance; keeping it — run a full "
                f"`--update-baseline` to regenerate and tag it")
            kept.append(row)
            continue
        if module not in known_modules:
            warnings.warn(
                f"pruning stale baseline row {row['name']!r}: its bench "
                f"module {module!r} is no longer in the registry")
            continue
        if module in ran_modules:
            warnings.warn(
                f"pruning stale baseline row {row['name']!r}: bench "
                f"module {module!r} ran but no longer emits it "
                f"(renamed/removed row)")
            continue
        kept.append(row)
    return kept + list(fresh)


def update_baseline(argv: list[str]) -> None:
    """Regenerate ``benchmarks/baseline.json`` from a fresh run.  With
    filters, only the matching rows are refreshed (merged by name into
    the committed file, stale rows pruned — see :func:`merge_baseline`);
    without, the whole baseline is replaced."""
    filters = [a for a in argv if not a.startswith("-")]
    rows, failed = run_benches(filters)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed} — baseline NOT "
                         f"updated")
    known = {name for name, _ in registry()}
    ran = {r["module"] for r in rows}
    if filters and os.path.exists(BASELINE_JSON):
        with open(BASELINE_JSON) as f:
            baseline = json.load(f)
        old_names = {r["name"] for r in baseline}
        merged = merge_baseline(baseline, rows, ran, known)
        replaced = sum(r["name"] in old_names for r in rows)
        pruned = len(baseline) + len(rows) - replaced - len(merged)
        print(f"merged {len(rows)} fresh rows into {BASELINE_JSON} "
              f"({replaced} replaced, {len(rows) - replaced} added, "
              f"{pruned} stale pruned, {len(merged)} total)")
    else:
        merged = rows
        print(f"replacing {BASELINE_JSON} with {len(rows)} fresh rows")
    with open(BASELINE_JSON, "w") as f:
        json.dump(merged, f, indent=2)
        f.write("\n")
    print(f"wrote {BASELINE_JSON}")


def _pop_trace_flag(argv: list[str]) -> str | None:
    """Extract ``--trace PATH`` / ``--trace=PATH`` from argv (mutating
    it); returns the path or None."""
    for i, arg in enumerate(argv):
        if arg == "--trace":
            if i + 1 >= len(argv):
                raise SystemExit("--trace requires a path argument")
            path = argv[i + 1]
            del argv[i:i + 2]
            return path
        if arg.startswith("--trace="):
            path = arg.split("=", 1)[1]
            del argv[i]
            return path
    return None


def main() -> None:
    argv = sys.argv[1:]
    trace_path = _pop_trace_flag(argv)
    if trace_path:
        from repro import obs
        obs.enable(chrome=trace_path)
        print(f"flight recorder on -> {trace_path}")
    try:
        if argv and argv[0] == "--gate":
            gate(argv[1:])
            return
        if argv and argv[0] == "--gate-suite":
            from .gates import run_suite
            run_suite(argv[1:])
            return
        # the gate suite's parent never imports JAX (each gate's child
        # process takes the device in turn); only the paths that run
        # benches compile, so only they turn the compile cache on
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        if argv and argv[0] == "--update-baseline":
            update_baseline(argv[1:])
            return

        filters = [a for a in argv if not a.startswith("-")]
        _, failed = run_benches(filters)
        if failed:
            raise SystemExit(f"benchmarks failed: {failed}")
    finally:
        if trace_path:
            from repro import obs
            obs.disable()       # flushes the Chrome trace
            print(f"wrote {trace_path}")


if __name__ == "__main__":
    main()
