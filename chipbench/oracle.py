"""The comparison that decides ``correct``.

A run's answers (a search's winner, a sweep's rows, a service's results)
are compared with the plain reference (``chipbench/reference``) by their
relative deviation, the widest over everything compared.  Each number
compared is a :class:`Check`: its reading beside its limit.  The limits
live in the cells' traffic files (``limits``); ``PERF.md`` gives the
readings each was set from.

``rel_dev`` and the stripping of unit loops before the reference sees a
mapping are copied from the program's chip smoke script.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from chipbench import common, reference

#: the modelled statistics compared for every answer
STATS = ("cycles", "energy_pj", "edp")


@dataclasses.dataclass
class Check:
    """One number compared, its limit, and whether it holds
    (``value <= limit``)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)

    def describe(self) -> str:
        verdict = "ok" if self.ok else "FAIL"
        return f"[check] {self.name} = {self.value!r} (limit {self.limit!r}) {verdict}"


def as_json(checks: list[Check]) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def rel_dev(a, b):
    """Elementwise |a - b| / |b| (0 where both are equal, inf included)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    same = (a == b) | (np.isnan(a) & np.isnan(b))
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
    return np.where(same, 0.0, r)


def worst(values) -> float:
    """The widest deviation of a list; inf or nan counts as inf, and so
    does an empty list, so a missing or broken answer can never pass."""
    out = math.inf if len(values) == 0 else 0.0
    for v in values:
        v = float(v)
        if math.isnan(v) or math.isinf(v):
            return math.inf
        out = max(out, v)
    return out


def ref_nest(loops, num_levels: int) -> reference.LoopNest:
    """A program mapping as the reference's plain data, unit-bound loops
    left out: the bucket program treats them as absent and the scalar
    model does not."""
    return reference.LoopNest(
        tuple(reference.Loop(lp.rank, int(lp.bound), int(lp.level),
                             bool(lp.spatial))
              for lp in loops if lp.bound > 1), num_levels)


def ref_workload(layer: dict, densities: dict | None) -> reference.Workload:
    """The reference's matmul of a layer (``M``, ``K``, ``N``) under JSON
    density specs."""
    return reference.matmul(int(layer["M"]), int(layer["K"]),
                            int(layer["N"]),
                            densities=common.densities(densities))


def evaluate(design_spec: dict, workload_of, loops, control: bool,
             check_capacity: bool = False) -> dict:
    """The reference's statistics of one mapping: in float64, or for the
    control in float32 (designs and workloads are rebuilt inside the
    lower precision, since their constants are cast when made).
    ``workload_of`` builds the reference workload."""
    kind = np.float32 if control else float
    with reference.num.precision(kind):
        des = reference.design(design_spec)
        out = reference.evaluate(des, workload_of(), loops,
                                 check_capacity=check_capacity)
    return {k: (bool(v) if k == "valid" else float(v))
            for k, v in out.items()}
