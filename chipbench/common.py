"""What the loops share: the program's designs and layers as a
configuration file names them, seeds derived from ``--seed``, and the
statistics of a window."""
from __future__ import annotations

import numpy as np


def program_design(spec: dict):
    """The program's design for a configuration's ``design`` entry (the
    reference builds its own from the same names)."""
    from repro.core import presets
    from repro.fleet.sweep import nm_design_for_weights
    if spec["preset"] == "nm_weights":
        return nm_design_for_weights(int(spec["n"]), int(spec["m"]))
    arch = getattr(presets, spec["arch"])()
    return getattr(presets, spec["preset"])(arch)


def densities(spec: dict | None) -> dict | None:
    """JSON density specs (lists) as the (kind, argument) tuples that the
    program and the reference both take."""
    if spec is None:
        return None
    return {t: (kind, arg) for t, (kind, arg) in spec.items()}


def program_workload(layer: dict):
    from repro.core import matmul
    return matmul(int(layer["M"]), int(layer["K"]), int(layer["N"]),
                  densities=densities(layer.get("densities")))


def constraints(cfg: dict, budget: int):
    """The mapspace of a configuration: its forced spatial factors."""
    from repro.core.mapper import MapspaceConstraints
    spatial = {int(lvl): dict(f) for lvl, f in cfg.get("spatial", {}).items()}
    return MapspaceConstraints(budget=budget, seed=0, spatial=spatial)


def derive(seed: int, *path: int) -> int:
    """A 31-bit integer drawn from ``--seed`` and a path of indices, so
    every search key and every sample is a function of the seed."""
    ss = np.random.SeedSequence([int(seed) % 2**63, *map(int, path)])
    return int(ss.generate_state(1)[0] % 2**31)


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng(derive(seed, *path))


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of raw values, linear between order
    statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def annotate(name: str, **kw):
    """A host span on the profiler's clock, around one call the benchmark
    makes; the trace reduction names idle gaps by these."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)
