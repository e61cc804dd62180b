"""Search convergence: stochastic strategies vs exhaustive enumeration
at equal (and 10x) evaluation budget on the Table 5 CPHC workload
(ResNet50 conv2_x as a GEMM), plus single-device vs multi-shard parity.

Emits quality-per-budget rows into ``BENCH_results.json`` (via
benchmarks.run) and writes the full per-generation trajectories to
``BENCH_search_convergence.json`` (uploaded next to the perf artifact by
CI).  The acceptance bar asserted here: the evolution strategy must
reach at most the best EDP that enumeration finds with a 10x larger
budget, and a run sharded over 8 simulated devices must match the
single-device run to <= 1e-6 relative.

  python -m benchmarks.bench_search_convergence            # full
  python -m benchmarks.bench_search_convergence --smoke    # CI smoke
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from repro.core import compile_stats, matmul
from repro.core.mapper import MapspaceConstraints, search
from repro.core.presets import (coordinate_list_design, scnn_like,
                                three_level_arch, two_level_arch)
from repro.search import SearchLog, run_search

from .common import emit

HOST_HZ = 3.0e9
CONV_JSON = "BENCH_search_convergence.json"

#: Table 5 CPHC workload: ResNet50 conv2_x as an im2col GEMM
CONV2X = ("conv2_x", 3136, 576, 64, 0.4, 0.55)

STRATEGIES = ("random", "hillclimb", "annealing", "es")
ES_BUDGET = 512
POP = 32


def _conv2x_setup():
    _, M, K, N, dA, dB = CONV2X
    wl = matmul(M, K, N, densities={"A": ("uniform", dA),
                                    "B": ("uniform", dB)})
    design = scnn_like(three_level_arch())
    cons = MapspaceConstraints(budget=ES_BUDGET, seed=0,
                               spatial={1: {"n": 8}})
    return design, wl, cons


def _fig1_setup(budget: int):
    """Fig. 1 coordinate-list preset on the generic two-level edge arch;
    the permutation constraint keeps the population on one template so
    the batched (and sharded) path carries the whole budget."""
    wl = matmul(64, 64, 64, densities={"A": ("uniform", 0.3),
                                       "B": ("uniform", 0.5)})
    design = coordinate_list_design(two_level_arch())
    cons = MapspaceConstraints(budget=budget, seed=0,
                               spatial={1: {"n": 8}},
                               permutations={0: ("n", "k", "m"),
                                             1: ("m", "n")})
    return design, wl, cons


def _parity_log(mesh) -> SearchLog:
    """The fixed-key search both sides of the shard-parity check run."""
    design, wl, cons = _fig1_setup(budget=256)
    res = run_search(design, wl, cons, strategy="es", key=123,
                     pop_size=64, mesh=mesh)
    return res.log


def _assert_monotone(log: SearchLog) -> None:
    traj = log.trajectory("best_edp")
    assert all(a >= b for a, b in zip(traj, traj[1:])), \
        f"best-so-far trajectory not monotone: {traj}"


def _shard_parity_rows() -> list[tuple[str, float, str]]:
    """Re-run the fixed-key search in a subprocess with 8 simulated host
    devices (population sharded via shard_map) and pin it against the
    in-process single-device vmap run."""
    single = _parity_log(mesh=None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # the child simulates 8 devices on the host CPU; it must never reach
    # for the accelerator, which this process already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    code = ("import jax, json\n"
            "assert len(jax.devices()) == 8, jax.devices()\n"
            "from benchmarks.bench_search_convergence import _parity_log\n"
            "print('PARITY=' + json.dumps(_parity_log('auto').to_dict()))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded parity subprocess failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    payload = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("PARITY=")][-1]
    sharded = SearchLog.from_dict(json.loads(payload[len("PARITY="):]))

    t1 = single.trajectory("best_edp")
    t8 = sharded.trajectory("best_edp")
    assert len(t1) == len(t8) > 0
    worst = max(abs(a - b) / max(1e-30, abs(a)) for a, b in zip(t1, t8))
    assert worst <= 1e-6, \
        f"single-device vs 8-shard trajectories diverge: {worst:.3e} rel"
    print(f"shard parity: 1 device vs 8 simulated shards, worst "
          f"best-EDP deviation {worst:.3e} rel over {len(t1)} generations")
    return [("search_shard_parity", 0.0,
             f"devices=8;generations={len(t1)};worst_rel={worst:.3e}")]


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    logs: dict[str, dict] = {}

    if smoke:
        design, wl, cons = _fig1_setup(budget=192)
        res = run_search(design, wl, cons, strategy="es", key=0,
                         pop_size=32)
        _assert_monotone(res.log)
        assert res.best is not None and res.best.result.valid
        logs["es_smoke"] = res.log.to_dict()
        print(f"smoke: es on Fig.1 preset, {res.evaluated} evals, "
              f"best EDP {res.best.edp:.4e}, monotone trajectory OK")
        rows.append(("search_smoke_es", 0.0,
                     f"evals={res.evaluated};best_edp={res.best.edp:.4e}"))
    else:
        design, wl, cons = _conv2x_setup()
        lname, M, K, N, _, _ = CONV2X
        computes = float(M) * K * N

        # enumeration baselines: equal budget and 10x budget
        enum_best = {}
        for mult in (1, 10):
            ecap = MapspaceConstraints(
                budget=ES_BUDGET * mult, seed=cons.seed,
                spatial=cons.spatial)
            t0 = time.perf_counter()
            with compile_stats.track() as st:
                res = search(design, wl, ecap)
            dt = time.perf_counter() - t0
            enum_best[mult] = res.best.edp if res.best else float("inf")
            cphc = res.evaluated * computes / (dt * HOST_HZ)
            print(f"enumeration x{mult:2d}: budget={ecap.budget:5d} "
                  f"best EDP={enum_best[mult]:.4e}  ({dt:.1f}s, "
                  f"CPHC={cphc:.0f}, {st.compiles} compiles)")
            rows.append((f"search_enum_x{mult}", dt * 1e6 / res.evaluated,
                         f"budget={ecap.budget};"
                         f"best_edp={enum_best[mult]:.6e};cphc={cphc:.0f};"
                         f"compiles={st.compiles}"))

        # stochastic strategies at the 1x budget.  Free-permutation
        # populations ride the bucketed engine: the whole mixed-
        # permutation population is one compiled program per strategy
        # run (compile counts reported below pin it)
        best = {}
        for strat in STRATEGIES:
            t0 = time.perf_counter()
            with compile_stats.track() as st:
                res = run_search(design, wl, cons, strategy=strat, key=0,
                                 pop_size=POP)
            dt = time.perf_counter() - t0
            _assert_monotone(res.log)
            best[strat] = res.best.edp if res.best else float("inf")
            logs[strat] = res.log.to_dict()
            cphc = res.evaluated * computes / (dt * HOST_HZ)
            print(f"{strat:>10s}: budget={res.evaluated:5d} "
                  f"best EDP={best[strat]:.4e}  ({dt:.1f}s, "
                  f"CPHC={cphc:.0f}, {st.compiles} compiles, "
                  f"{st.scalar_evals} scalar evals)")
            rows.append((f"search_{strat}", dt * 1e6 / res.evaluated,
                         f"budget={res.evaluated};"
                         f"best_edp={best[strat]:.6e};cphc={cphc:.0f};"
                         f"compiles={st.compiles};"
                         f"scalar_evals={st.scalar_evals}"))

        # acceptance: ES at budget B <= enumeration at 10B
        ratio = best["es"] / enum_best[10]
        print(f"\nES@{ES_BUDGET} vs enumeration@{ES_BUDGET * 10}: "
              f"{best['es']:.4e} vs {enum_best[10]:.4e} "
              f"({ratio:.3f}x; <= 1.0 required)")
        assert best["es"] <= enum_best[10], (
            f"evolution strategy (EDP {best['es']:.4e}) worse than "
            f"enumeration with 10x budget ({enum_best[10]:.4e})")
        rows.append(("search_es_vs_enum10x", 0.0,
                     f"layer={lname};es_edp={best['es']:.6e};"
                     f"enum10x_edp={enum_best[10]:.6e};ratio={ratio:.4f}"))

        rows.extend(_shard_parity_rows())

    with open(CONV_JSON, "w") as f:
        json.dump(logs, f, indent=2)
        f.write("\n")
    print(f"wrote {CONV_JSON} ({len(logs)} trajectories)")
    return rows


if __name__ == "__main__":
    emit(run(smoke="--smoke" in sys.argv))
