"""The reduction from a profiler trace to busy time, idle share, top
device ops and named idle gaps, on a small recorded trace."""
import json
import pathlib

import pytest

from chipbench import trace_reduce

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture
def small():
    raw = json.loads(DATA.read_text())
    return {"devices": {d: [tuple(e) for e in evs]
                        for d, evs in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_union_merges_and_clips():
    got = trace_reduce.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10)
    assert got == [(1, 4), (5, 10)]


def test_busy_and_idle_share(small):
    red = trace_reduce.reduce(small)
    # device 0: [100, 400) + [600, 700) + [950, 1000) = 450 ns in the
    # window; device 1: 100 ns; device 2 ran nothing and is not counted
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((450 + 100) / 2 * 1e-9)
    assert red["idle_pct"] == pytest.approx(100 * (1 - 275 / 1000))


def test_top_ops_are_clipped_and_averaged(small):
    ops = dict(trace_reduce.reduce(small)["device_ops"])
    assert ops["fusion.1"] == pytest.approx((200 + 50 + 100) / 2 * 1e-9)
    assert ops["fusion.2"] == pytest.approx(150 / 2 * 1e-9)
    assert ops["copy.3"] == pytest.approx(100 / 2 * 1e-9)
    order = [n for n, _ in trace_reduce.reduce(small)["device_ops"]]
    assert order[0] == "fusion.1"


def test_idle_gaps_are_named_by_the_host_span(small):
    gaps = dict(trace_reduce.reduce(small)["idle_gaps"])
    # device 0's gaps, each named at its midpoint: [0,100) inside the
    # first search, [400,600) between the calls (midpoint 500, where the
    # first search has ended), [700,950) inside the second search
    assert gaps["bench.search"] == pytest.approx((100 + 250) * 1e-9)
    assert gaps["bench.window"] == pytest.approx(200e-9)
    assert len(gaps) == 2


def test_no_device_events_reads_nothing():
    red = trace_reduce.reduce({"devices": {}, "host": []})
    assert red["busy_s"] == 0.0 and red["devices"] == 0
    assert red["idle_pct"] is None


def test_load_reads_a_profiler_file(tmp_path):
    """A real ``.xplane.pb`` from the CPU: the benchmark's host spans come
    back on the profiler's clock."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.search"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    trace = trace_reduce.load(str(path))
    names = [n for n, _, _ in trace["host"]]
    assert "bench.window" in names and "bench.search" in names
    (s, e), = [(s, e) for n, s, e in trace["host"] if n == "bench.window"]
    (s2, e2), = [(s, e) for n, s, e in trace["host"] if n == "bench.search"]
    assert s <= s2 < e2 <= e
