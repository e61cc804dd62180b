"""Every name in BENCHMARK.json resolves to its file, and the file holds
what the harness reads from it."""
import importlib.util
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    path = ROOT / cfg["file"]
    assert path.is_file() and BENCH in path.parents
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert "source" in data and "assumed" in data


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    loop = _load(BENCH / "loops" / f"{mix['loop']}.py")
    for fn in ("setup", "window", "end_to_end", "modelled", "check"):
        assert callable(getattr(loop, fn))
    assert "limits" in mix
    reports = [m for m in SPEC["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in {m["name"] for m in reports} and len(reports) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_reader(metric):
    reader = _load(BENCH / "metrics" / f"{metric['name']}.py")
    # a reader that finds nothing to read returns nothing
    assert reader.read({}) is None
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric["workloads"]) <= cells


def test_names_and_keys():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert SPEC["command"][1] == "chipbench/run.py"
    assert (ROOT / SPEC["command"][1]).is_file()
