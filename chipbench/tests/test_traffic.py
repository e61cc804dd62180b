"""Every traffic generator is a function of ``--seed``: the same seed gives
the same work, and other seeds the same multiset of work in another
order."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import common

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SEEDS = (0, 2**31 + 12345, 9_000_000_001)


def _loop_module(loop):
    import importlib.util
    path = ROOT / "chipbench" / "loops" / f"{loop}.py"
    spec = importlib.util.spec_from_file_location(loop, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_derive_is_deterministic_and_31_bit():
    for s in SEEDS:
        keys = [common.derive(s, 2, i) for i in range(50)]
        assert keys == [common.derive(s, 2, i) for i in range(50)]
        assert all(0 <= k < 2**31 for k in keys)
        assert len(set(keys)) == 50
    assert common.derive(SEEDS[0], 2, 0) != common.derive(SEEDS[1], 2, 0)


MIXES = sorted((ROOT / "chipbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize(
    "path", [p for p in MIXES
             if json.loads(p.read_text())["loop"] == "open_service"],
    ids=lambda p: p.stem)
def test_open_loop_schedule(path):
    mix = json.loads(path.read_text())
    drv = _loop_module("open_service")
    seconds = SPEC["run_seconds"]
    plans = {s: drv.schedule(mix, seconds, s, 4) for s in SEEDS}
    assert plans[SEEDS[0]] == drv.schedule(mix, seconds, SEEDS[0], 4)
    assert plans[SEEDS[0]] != plans[SEEDS[1]]
    first = plans[SEEDS[0]]
    for plan in plans.values():
        assert len(plan) == len(first)
        due = [p[0] for p in plan]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
        for k in (1, 2, 3):
            assert sorted(p[k] for p in plan) == sorted(p[k] for p in first)
        gaps = np.diff([0.0] + due)
        assert len(gaps) == len(plan)
    # the burst: the rate in the first second is about factor x the rest
    b = mix["burst"]
    in_burst = sum(p[0] < b["seconds"] for p in first)
    rest = sum(p[0] >= b["seconds"] for p in first) / (seconds - b["seconds"])
    assert in_burst == pytest.approx(b["factor"] * rest, rel=0.2)
