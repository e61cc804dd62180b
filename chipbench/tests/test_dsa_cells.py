"""The cells that sweep DeepSeek-V3.2-Exp's sparse-attention decode and
that shard the search's population over four chips: ``correct`` holds
for a sound run, rehearsed on the CPU (the sweep on the model's reduced
preset, the search unsharded on one device), and comes out false for
the control and for a fault planted under the timed path.  The readers
of the sweep's new metrics read hand-built spans."""
import importlib.util
import json
import pathlib

import pytest

from chipbench import run
from chipbench.tests import faults_dsa
from repro.obs.trace import Span

METRICS = pathlib.Path(__file__).parent.parent / "metrics"
CELLS = ("search.resnet50.sharded4", "sweep.dsv32.decode128k")


def _run(capsys, cell: str, *extra: str) -> dict:
    rc = run.main(["--workload", cell, "--seed", "3000000019",
                   "--seconds", "1", "--trace", "0", "--rehearse", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_sound_run_is_correct(capsys, cell):
    line = _run(capsys, cell)
    assert line["correct"], line["checks"]
    assert "metrics" not in line and "device" not in line


@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_control_is_not_correct(capsys, cell):
    line = _run(capsys, cell, "--control")
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults_dsa.DSA_FAULTS))
def test_dsa_fault(capsys, monkeypatch, fault):
    faults_dsa.DSA_FAULTS[fault](monkeypatch.setattr)
    line = _run(capsys, "sweep.dsv32.decode128k")
    assert not line["correct"], line["checks"]
    assert line["checks"]["ctx_crossover_wrong"]["value"] > 0


def test_sharded_search_device_best_altered(capsys, monkeypatch):
    """The host loop's per-generation best is what the device evaluated:
    a best 1% off is caught."""
    from repro.search import runner
    real = runner.GenerationRecord

    def altered(**kw):
        kw["best_cycles"] = kw["best_cycles"] * (1 + 1e-2)
        return real(**kw)

    monkeypatch.setattr(runner, "GenerationRecord", altered)
    line = _run(capsys, "search.resnet50.sharded4")
    assert not line["correct"]
    assert line["checks"]["device_best_rel"]["value"] > 1e-3


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, dur, sid, **attrs):
    return Span(name=name, t_start=0.0, t_end=dur, tid=0, depth=0,
                attrs=attrs, sid=sid)


def test_dsa_sweep_readers():
    spans = [_span("fleet.sweep", 0.05, 1), _span("fleet.sweep", 0.05, 2),
             _span("fleet.option", 0.01, 3, option="dense", selected=2),
             _span("fleet.option", 0.01, 4, option="nm-2:4", selected=0),
             _span("fleet.option", 0.01, 5, option="dsa-skip", selected=2),
             _span("fleet.option", 0.01, 6, option="dense", selected=2),
             _span("fleet.option", 0.01, 7, option="dsa-skip", selected=2),
             _span("fleet.ctx_crossover", 0.004, 8, rows=28),
             _span("fleet.ctx_crossover", 0.006, 9, rows=28)]
    assert _reader("ctxcross_ms.sweep")({"spans": spans}) \
        == pytest.approx(5.0)
    assert _reader("selrows.sweep")({"spans": spans}) == 4.0
    # a program whose spans do not count selected rows gives no reading
    plain = [s for s in spans if s.name == "fleet.sweep"] + [
        _span("fleet.option", 0.01, 10, option="dense")]
    assert _reader("selrows.sweep")({"spans": plain}) is None
    assert _reader("ctxcross_ms.sweep")({"spans": plain}) is None
