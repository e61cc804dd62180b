"""Program spans in the benchmark's readings: idle gaps named by the
innermost program span around them, and the readers of the search's
entry and validation times and of the sweep's lowering, dispatch and
calls, on hand-built records."""
import importlib.util
import json
import pathlib
import types

import pytest

from chipbench import trace_reduce
from repro.obs.trace import Span

HERE = pathlib.Path(__file__).parent
METRICS = HERE.parent / "metrics"
#: host span names that belong to the program, not to the benchmark
PROGRAM = ("search.", "engine.", "fleet.", "dse.")


def _reader(name: str):
    path = METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def traced():
    raw = json.loads((HERE / "data" / "trace_spans.json").read_text())
    return {"devices": {d: [tuple(e) for e in evs]
                        for d, evs in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_gaps_are_named_by_the_innermost_program_span(traced):
    gaps = dict(trace_reduce.reduce(traced)["idle_gaps"])
    # device 0's gaps, each named at its midpoint: [20,140) in
    # search.prepare; [160,320) in engine.dispatch (inside engine.eval
    # inside search.chunk); [580,700) and [720,960) in search.validate
    assert gaps == pytest.approx({"search.prepare": 120e-9,
                                  "engine.dispatch": 160e-9,
                                  "search.validate": 360e-9})
    named = sum(v for n, v in gaps.items() if n.startswith(PROGRAM))
    assert named == pytest.approx(sum(gaps.values()))


def test_program_spans_leave_the_window_and_busy_time_alone(traced):
    red = trace_reduce.reduce(traced)
    bench = dict(traced, host=[h for h in traced["host"]
                               if h[0].startswith("bench.")])
    alone = trace_reduce.reduce(bench)
    for key in ("busy_s", "window_s", "idle_pct", "devices", "device_ops"):
        assert red[key] == alone[key], key
    assert red["busy_s"] == pytest.approx(360e-9)
    assert red["idle_pct"] == pytest.approx(64.0)
    # without program spans every gap falls to the benchmark's call
    assert dict(alone["idle_gaps"]) == pytest.approx(
        {"bench.search": 640e-9})


def _search(prepare_s, validate_s):
    log = types.SimpleNamespace(timing={"prepare_s": prepare_s,
                                        "validate_s": validate_s})
    return {"result": types.SimpleNamespace(log=log)}


def test_search_entry_and_validation_readers():
    records = {"searches": [_search(0.020, 0.008), _search(0.024, 0.006),
                            _search(0.022, 0.010)]}
    assert _reader("prepare_ms.search")(records) == pytest.approx(22.0)
    assert _reader("validate_ms.search")(records) == pytest.approx(8.0)


def test_search_readers_read_nothing_from_an_older_log():
    old = types.SimpleNamespace(timing={"wall_s": 0.05, "chunks": []})
    records = {"searches": [{"result": types.SimpleNamespace(log=old)}]}
    assert _reader("prepare_ms.search")(records) is None
    assert _reader("validate_ms.search")(records) is None


def _span(name, t0, t1, sid, parent=None):
    return Span(name=name, t_start=t0, t_end=t1, tid=1, depth=0, attrs={},
                sid=sid, parent=parent)


def _sweeps():
    """Two sweeps.  In the first, batch 1 (10 ms) lowers two groups and
    so holds two calls: an eval of 6 ms (dispatch 2, fetch 3.5) and one
    of 1 ms (dispatch 0.5); batch 5 (4 ms) holds an eval of 3 ms
    (dispatch 1, fetch 1.5).  In the second, batch 11 (5 ms) holds an
    eval of 2 ms (dispatch 0.5, fetch 1)."""
    return [
        _span("fleet.sweep", 0.000, 0.050, 20),
        _span("fleet.extract", 0.001, 0.002, 21, 20),
        _span("engine.batch", 0.010, 0.020, 1, 20),
        _span("engine.eval", 0.011, 0.017, 2, 1),
        _span("engine.dispatch", 0.011, 0.013, 3, 2),
        _span("engine.fetch", 0.013, 0.0165, 4, 2),
        _span("engine.eval", 0.018, 0.019, 9, 1),
        _span("engine.dispatch", 0.018, 0.0185, 10, 9),
        _span("engine.batch", 0.030, 0.034, 5, 20),
        _span("engine.eval", 0.030, 0.033, 6, 5),
        _span("engine.dispatch", 0.030, 0.031, 7, 6),
        _span("engine.fetch", 0.031, 0.0325, 8, 6),
        _span("fleet.sweep", 0.060, 0.100, 30),
        _span("engine.batch", 0.070, 0.075, 11, 30),
        _span("engine.eval", 0.071, 0.073, 12, 11),
        _span("engine.dispatch", 0.071, 0.0715, 13, 12),
        _span("engine.fetch", 0.0715, 0.0725, 14, 12),
    ]


def test_sweep_lowering_is_batch_self_time():
    # self time: (10 - 6 - 1) + (4 - 3) + (5 - 2) = 7 ms over 2 sweeps;
    # the grandchildren (dispatch, fetch) are not taken off twice
    assert _reader("lower_ms.sweep")({"spans": _sweeps()}) == \
        pytest.approx(3.5)


def test_sweep_dispatch_and_calls():
    records = {"spans": _sweeps()}
    # (2 + 0.5 + 1 + 0.5) ms of dispatch over 2 sweeps, 4 calls over 2
    assert _reader("dispatch_ms.sweep")(records) == pytest.approx(2.0)
    assert _reader("calls.sweep")(records) == pytest.approx(2.0)


def test_sweep_readers_read_nothing_without_engine_spans():
    """The spans a program without engine.batch / engine.dispatch leaves:
    its Span records carry no ids either."""
    old = [types.SimpleNamespace(name="fleet.sweep", dur=0.05),
           types.SimpleNamespace(name="fleet.extract", dur=0.001),
           types.SimpleNamespace(name="engine.eval", dur=0.003)]
    for name in ("lower_ms.sweep", "dispatch_ms.sweep", "calls.sweep"):
        assert _reader(name)({"spans": old}) is None, name
