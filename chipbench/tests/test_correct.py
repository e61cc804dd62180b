"""``correct`` comes out false where it has to: for the control (the
float32 reference in the program's place) and for each fault a cell can
have, planted under the timed path.  The runs skip the look for a chip
(``--rehearse``) and drive the rest of a run at the mixes' rehearsal
sizes on the CPU."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import run
from chipbench.tests import faults

HELD_OUT = pathlib.Path(__file__).parent / "data" / "held_out.json"

CELLS = {"search": "search.resnet50.fused", "sweep": "sweep.fleet6",
         "service": "service.resnet50.open"}


@pytest.fixture(autouse=True)
def with_held_out_cells(tmp_path, monkeypatch):
    """BENCHMARK.json with the held-out cells added, so their loops are
    checked like the others."""
    spec = json.loads(run.SPEC.read_text())
    for key, entries in json.loads(HELD_OUT.read_text()).items():
        if key in spec:
            spec[key] = spec[key] + entries
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    monkeypatch.setattr(run, "SPEC", path)


def _run(capsys, cell: str, *extra: str) -> dict:
    rc = run.main(["--workload", CELLS[cell], "--seed", "3000000019",
                   "--seconds", "1", "--trace", "0", "--rehearse",
                   *extra])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(capsys, cell):
    line = _run(capsys, cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert "metrics" not in line and "device" not in line


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(capsys, cell):
    line = _run(capsys, cell, "--control")
    assert not line["correct"], line["checks"]


def _scale(out: dict, keys=("cycles", "edp")) -> dict:
    out = dict(out)
    for k in keys:
        if k in out:
            out[k] = np.asarray(out[k]) * (1 + 1e-2)
    return out


def test_search_answer_altered(capsys, monkeypatch):
    from repro.search import fused
    real = fused.FusedProgram.invoke_chunk

    def altered(self, carry, length):
        carry, ys = real(self, carry, length)
        return carry, _scale(ys, ("best_cycles", "best_edp"))

    monkeypatch.setattr(fused.FusedProgram, "invoke_chunk", altered)
    assert not _run(capsys, "search")["correct"]


@pytest.mark.parametrize("fault", sorted(faults.SEARCH_FAULTS))
def test_search_fault(capsys, monkeypatch, fault):
    faults.SEARCH_FAULTS[fault](monkeypatch.setattr)
    line = _run(capsys, "search")
    monkeypatch.undo()
    faults._fresh_programs()
    assert not line["correct"], line["checks"]


def test_sweep_answer_altered(capsys, monkeypatch):
    from repro.fleet import sweep
    real = sweep._evaluate_shapes

    def altered(option, shapes, **kw):
        return [_scale(r) for r in real(option, shapes, **kw)]

    monkeypatch.setattr(sweep, "_evaluate_shapes", altered)
    assert not _run(capsys, "sweep")["correct"]


def test_sweep_shape_dropped(capsys, monkeypatch):
    import dataclasses

    from repro.fleet import sweep
    real = sweep.extract_fleet

    def dropped(*a, **kw):
        return [dataclasses.replace(net, matmuls=net.matmuls[:-1])
                for net in real(*a, **kw)]

    monkeypatch.setattr(sweep, "extract_fleet", dropped)
    assert not _run(capsys, "sweep")["correct"]


def test_service_answer_altered(capsys, monkeypatch):
    from repro.dse import service
    real = service.EvaluationService._invoke

    def altered(self, *a, **kw):
        return _scale(real(self, *a, **kw))

    monkeypatch.setattr(service.EvaluationService, "_invoke", altered)
    assert not _run(capsys, "service")["correct"]


def test_service_half_batch_left_out(capsys, monkeypatch):
    from repro.dse import service
    real = service.EvaluationService._invoke

    def halved(self, model, bounds, ids, *a, **kw):
        half = max(1, len(bounds) // 2)
        res = real(self, model, bounds[:half],
                   None if ids is None else ids[:half], *a, **kw)
        # the rows left out take the answers of the ones kept
        idx = np.arange(len(bounds)) % half
        return {k: np.asarray(v)[idx] for k, v in res.items()}

    monkeypatch.setattr(service.EvaluationService, "_invoke", halved)
    assert not _run(capsys, "service")["correct"]
