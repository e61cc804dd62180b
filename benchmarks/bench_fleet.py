"""Fleet sweep + advisor-agreement benchmarks (beyond-paper: the Sec. 7
DSE loop scaled to the whole LM fleet, with its verdicts validated
against kernels that actually run).

Two CI-gated claims:

* **fleet-compile-gate**: the full 10-config fleet sweep — every
  per-layer matmul of every ``repro/configs/`` architecture, prefill +
  decode, production-mesh shards, dense + N:M options — compiles at
  most ``FleetReport.compile_bound`` programs (one bucket per design
  point: config- and layer-count independent), touches the scalar path
  zero times, and dedupes repeated layer shapes (``dedup_evals > 0``).
  A repeat sweep over a config subset must add ZERO compiles (shape-
  independent density caps -> warm programs).
* **advisor-agreement**: on the REDUCED configs, the advisor/model
  verdict SIGNS agree with measured interpret-mode Pallas kernels —
  skip saves wall-clock (~1/density), gate does not (taxonomy: GATE
  saves energy, not time), skip beats gate, and the N:M verdict's
  traffic win matches the measured packed-weight byte ratio with a
  correct kernel.  Any sign disagreement fails.

  python -m benchmarks.bench_fleet                    # full (both + crossover)
  python -m benchmarks.bench_fleet --compile-gate     # CI gate
  python -m benchmarks.bench_fleet --agreement-smoke  # CI gate

Both entry points write ``BENCH_fleet.json`` (uploaded as a CI
artifact) with the full per-layer verdict rows / agreement rows.
"""
from __future__ import annotations

import json
import sys

from repro.core import compile_stats
from repro.fleet.sweep import default_options, dsa_option, fleet_sweep
from repro.fleet.validate import (agreement_summary, validate_fleet)

from .common import emit

FLEET_JSON = "BENCH_fleet.json"
#: host clock for the CPHC-family throughput metric (matches
#: bench_table5_cphc)
HOST_HZ = 3.0e9


def _write_fleet_json(sweep: dict | None, agreement: list | None) -> None:
    """Merge-write BENCH_fleet.json so the compile-gate and agreement
    steps (separate processes in CI) both land in one artifact."""
    try:
        with open(FLEET_JSON) as f:
            blob = json.load(f)
    except (OSError, json.JSONDecodeError):
        blob = {}
    if sweep is not None:
        blob["sweep"] = sweep
    if agreement is not None:
        blob["agreement"] = agreement
    with open(FLEET_JSON, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {FLEET_JSON}")


def _sweep_row(name: str, rep, st) -> tuple[str, float, str]:
    cphc = rep.total_dense_computes / max(1.0, rep.wall_seconds * HOST_HZ)
    us = rep.wall_seconds * 1e6 / max(1, rep.total_entries)
    return (name, us,
            f"entries={rep.total_entries};unique={rep.unique_shapes};"
            f"options={len(rep.option_names)};"
            f"compiles={st.compiles};bound={rep.compile_bound};"
            f"program_shares={st.program_shares};"
            f"dedup_evals={st.dedup_evals};"
            f"scalar_evals={st.scalar_evals};"
            f"wall_s={rep.wall_seconds:.2f};"
            f"cphc_fleet={cphc:.0f}")


def _assert_sweep(rep, st) -> None:
    assert st.compiles <= rep.compile_bound, (
        f"fleet sweep compiled {st.compiles} programs, structural bound "
        f"is {rep.compile_bound} (one bucket per design point) — the "
        f"single-bucket tpu_mapping or program sharing regressed "
        f"(by kind: {st.compiles_by_kind})")
    assert st.scalar_evals == 0, (
        f"fleet sweep fell back to the scalar path for "
        f"{st.scalar_evals} evaluations")
    assert st.dedup_evals > 0, (
        "fleet sweep deduplicated nothing — repeated layer shapes "
        "(identical transformer blocks) should collapse before "
        "evaluation")


def compile_gate() -> list[tuple[str, float, str]]:
    """Full fleet sweep (every config) under a hard, config- and layer-count
    independent compile budget, then a subset re-sweep that must be
    entirely warm (zero additional compiles)."""
    from repro.configs import ARCH_NAMES, get_config
    with compile_stats.track() as st:
        rep = fleet_sweep()          # all configs, prefill+decode
    print(rep.summary())
    _assert_sweep(rep, st)
    # one program per design point of the options the sweep ran
    n_designs = sum(len(o.designs())
                    for o in (*default_options(), dsa_option())
                    if o.name in rep.option_names)
    assert rep.compile_bound == n_designs, (
        f"compile bound {rep.compile_bound} != design count {n_designs}:"
        f" tpu_mapping no longer lowers every fleet shape into one "
        f"bucket per design")

    # programs are keyed by whether their rows hold a selection (DSA):
    # a subset with the same density families reuses every program
    subset = ARCH_NAMES[:2] + tuple(
        n for n in ARCH_NAMES if get_config(n).dsa is not None)
    with compile_stats.track() as st2:
        rep2 = fleet_sweep(subset)
    print(f"subset re-sweep ({len(subset)} configs): {st2.compiles} "
          f"additional compiles, {st2.program_shares} program shares")
    assert st2.compiles == 0, (
        f"a {len(subset)}-config subset sweep re-compiled "
        f"{st2.compiles} programs after the full fleet sweep — programs "
        f"stopped being shape-independent (caps/bucket key regressed)")
    # dedup is NOT asserted here: a 2-config subset can legitimately
    # have all-unique per-device shapes (dedup wins come from repeated
    # layers and cross-config collisions, which the full sweep pins)
    assert st2.scalar_evals == 0, (
        f"subset re-sweep fell back to the scalar path "
        f"{st2.scalar_evals} times")
    assert rep2.total_entries > 0

    _write_fleet_json(rep.to_json(), None)
    row = _sweep_row("fleet_compile_gate", rep, st)
    return [(row[0], 0.0,
             row[2] + f";subset_compiles={st2.compiles}")]


def agreement_smoke(reps: int = 5) -> list[tuple[str, float, str]]:
    """REDUCED-config validation harness, all arms; any verdict /
    measurement sign disagreement fails."""
    rows = validate_fleet(reps=reps)
    print(agreement_summary(rows))
    bad = [r for r in rows if not r.agree]
    _write_fleet_json(None, [r.as_dict() for r in rows])
    assert not bad, (
        f"{len(bad)} advisor verdicts disagree in sign with measured "
        f"kernels:\n" + "\n".join(
            f"  {r.config} {r.layer} {r.arm}: predicted "
            f"{r.predicted:.3f} measured {r.measured:.3f} ({r.detail})"
            for r in bad))
    arms = sorted({r.arm for r in rows})
    cells = len({(r.M, r.K, r.N) for r in rows})
    return [("fleet_agreement", 0.0,
             f"rows={len(rows)};arms={len(arms)};cells={cells};"
             f"disagreements=0")]


def run() -> list[tuple[str, float, str]]:
    """Full mode: fleet sweep WITH crossover grids + agreement rows."""
    with compile_stats.track() as st:
        rep = fleet_sweep(crossover=True)
    print(rep.summary())
    _assert_sweep(rep, st)
    nm_cross = [v.get("nm-2:4") for v in rep.crossover.values()]
    located = sum(1 for v in nm_cross if v is not None)
    print(f"crossover: nm-2:4 pays below some M for {located}/"
          f"{len(nm_cross)} weight (K, N) shapes")

    agree_rows = agreement_smoke()
    _write_fleet_json(rep.to_json(), None)
    rows = [_sweep_row("fleet_sweep", rep, st)]
    rows.append(("fleet_crossover", 0.0,
                 f"kn_shapes={len(nm_cross)};nm24_wins={located}"))
    rows.extend(agree_rows)
    return rows


if __name__ == "__main__":
    if "--compile-gate" in sys.argv:
        emit(compile_gate())
    elif "--agreement-smoke" in sys.argv:
        emit(agreement_smoke())
    else:
        emit(run())
