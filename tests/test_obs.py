"""Flight-recorder suite: span tracer, metrics registry, sinks, Chrome
export + schema validation, engine compile/eval attribution, and the
SearchLog timing contract."""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import compile_stats
from repro.obs import metrics
from repro.obs.export import (chrome_trace_events, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.trace import _NULL_SPAN


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Every test starts and ends with tracing off and empty metrics."""
    obs.disable()
    metrics.reset()
    yield
    obs.disable()
    metrics.reset()


# ----------------------------------------------------------------------
# tracer
# ----------------------------------------------------------------------
def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    cm = obs.span("anything", big_attr=list(range(100)))
    assert cm is _NULL_SPAN          # no per-call allocation
    with cm as sp:
        sp.set(ignored=1)            # handle accepts attrs, drops them
    assert obs.tracer() is None


def test_span_nesting_records_depth_and_containment():
    tr = obs.enable()
    with obs.span("outer", a=1):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    inner, outer = tr.find("inner"), tr.find("outer")
    assert len(inner) == 2 and len(outer) == 1
    assert all(s.depth == 1 for s in inner)
    assert outer[0].depth == 0
    assert outer[0].attrs == {"a": 1}
    for s in inner:                  # children contained in the parent
        assert outer[0].t_start <= s.t_start
        assert s.t_end <= outer[0].t_end
    # children finish first, so they are recorded first
    assert [s.name for s in tr.spans] == ["inner", "inner", "outer"]
    assert tr.total("inner") <= outer[0].dur + 1e-9


def test_span_handle_set_attaches_result_attrs():
    tr = obs.enable()
    with obs.span("work", phase="start") as sp:
        sp.set(result=42)
    (span,) = tr.spans
    assert span.attrs == {"phase": "start", "result": 42}


def test_span_recorded_on_exception():
    tr = obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert [s.name for s in tr.spans] == ["boom"]
    # the stack unwound: a new span starts back at depth 0
    with obs.span("after"):
        pass
    assert tr.find("after")[0].depth == 0


def test_thread_local_span_stacks_do_not_interleave():
    tr = obs.enable()
    barrier = threading.Barrier(2)

    def work(name):
        with obs.span(f"{name}.outer"):
            barrier.wait(timeout=10)
            with obs.span(f"{name}.inner"):
                barrier.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(n,))
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    # both threads ran concurrently, yet each sees its own stack: every
    # outer span is depth 0, every inner span depth 1
    for name in ("a", "b"):
        assert tr.find(f"{name}.outer")[0].depth == 0
        assert tr.find(f"{name}.inner")[0].depth == 1
    assert len({s.tid for s in tr.spans}) == 2


def test_sid_and_parent_round_trip_through_sinks(tmp_path):
    jl, ch = tmp_path / "events.jsonl", tmp_path / "trace.json"
    tr = obs.enable(jsonl=str(jl), chrome=str(ch))
    with obs.span("outer"):
        with obs.span("inner"):
            with obs.span("leaf"):
                pass
        with obs.span("inner"):
            pass
    spans = list(tr.spans)
    obs.disable()
    (outer,) = [s for s in spans if s.name == "outer"]
    inners = [s for s in spans if s.name == "inner"]
    (leaf,) = [s for s in spans if s.name == "leaf"]
    assert len({s.sid for s in spans}) == 4
    assert outer.parent is None
    assert [s.parent for s in inners] == [outer.sid, outer.sid]
    assert leaf.parent == inners[0].sid
    # self time from the links: a span less its direct children
    own = outer.dur - sum(s.dur for s in spans if s.parent == outer.sid)
    assert 0 <= own <= outer.dur
    want = sorted((s.name, s.sid, s.parent) for s in spans)
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert sorted((ln["name"], ln["sid"], ln["parent"])
                  for ln in lines) == want
    obj = json.loads(ch.read_text())
    assert validate_chrome_trace(obj) == []
    assert sorted((e["name"], e["sid"], e["parent"])
                  for e in obj["traceEvents"] if e["ph"] == "X") == want


# ----------------------------------------------------------------------
# REPRO_TRACE switch + sinks
# ----------------------------------------------------------------------
def test_env_off_words_keep_tracing_disabled():
    for word in ("", "0", "off", "false", "no"):
        assert obs.configure_from_env({"REPRO_TRACE": word}) is None
        assert not obs.enabled()
    assert obs.configure_from_env({}) is None


def test_env_memory_words_enable_in_memory():
    tr = obs.configure_from_env({"REPRO_TRACE": "1"})
    assert tr is obs.tracer() is not None
    with obs.span("x"):
        pass
    assert len(tr.spans) == 1


def test_env_unrecognized_warns_and_enables(recwarn):
    tr = obs.configure_from_env({"REPRO_TRACE": "bogus-value"})
    assert tr is not None
    assert any("REPRO_TRACE" in str(w.message) for w in recwarn.list)


def test_jsonl_sink_streams_spans(tmp_path):
    path = tmp_path / "events.jsonl"
    obs.configure_from_env({"REPRO_TRACE": str(path)})
    with obs.span("outer", k="v"):
        with obs.span("inner"):
            pass
    obs.disable()
    lines = [json.loads(ln) for ln in
             path.read_text().strip().splitlines()]
    assert [ln["name"] for ln in lines] == ["inner", "outer"]
    assert lines[1]["attrs"] == {"k": "v"}
    assert all(ln["dur"] >= 0 and ln["ts"] >= 0 for ln in lines)
    assert lines[0]["depth"] == 1


def test_env_chrome_path_flushes_on_disable(tmp_path):
    path = tmp_path / "trace.json"
    obs.configure_from_env({"REPRO_TRACE": str(path)})
    with obs.span("work", answer=42):
        pass
    assert not path.exists()         # written at disable/exit, not live
    obs.disable()
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] == "X"]
    assert names == ["work"]


# ----------------------------------------------------------------------
# Chrome export + schema validation
# ----------------------------------------------------------------------
def test_chrome_export_schema_valid_across_threads(tmp_path):
    tr = obs.enable()

    def work():
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                pass

    threads = [threading.Thread(target=work) for _ in range(3)]
    with obs.span("main", shape=(4, 7), arr=np.int64(3)):
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    path = write_chrome_trace(tmp_path / "trace.json", tr.spans,
                              metrics.snapshot())
    obj = json.loads(open(path).read())
    assert validate_chrome_trace(obj) == []
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 7                       # 3x2 thread spans + main
    # one track per thread IDENT (the OS may reuse an exited worker's
    # ident, so 2..4 distinct tracks; main's is always its own)
    assert 2 <= len({e["tid"] for e in xs}) <= 4
    main = next(e for e in xs if e["name"] == "main")
    # attrs are JSON-clean: tuples -> lists, numpy -> python
    assert main["args"] == {"shape": [4, 7], "arr": 3}


def test_validation_catches_broken_traces():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": []}) != []
    ok = {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 0, "tid": 0}
    assert validate_chrome_trace({"traceEvents": [ok]}) == []
    bad_dur = dict(ok, dur=-5)
    assert any("bad dur" in e for e in
               validate_chrome_trace({"traceEvents": [bad_dur]}))
    missing = {"name": "a", "ph": "X", "ts": 0}
    assert any("missing keys" in e for e in
               validate_chrome_trace({"traceEvents": [missing]}))
    # partial overlap on one track = unbalanced spans
    overlap = [dict(ok, name="p", ts=0, dur=10),
               dict(ok, name="q", ts=5, dur=10)]
    assert any("unbalanced" in e for e in
               validate_chrome_trace({"traceEvents": overlap}))
    # proper nesting on one track, disjoint on another: fine
    nested = [dict(ok, name="p", ts=0, dur=10),
              dict(ok, name="q", ts=2, dur=3),
              dict(ok, name="r", ts=6, dur=2),
              dict(ok, name="s", ts=0, dur=4, tid=1)]
    assert validate_chrome_trace({"traceEvents": nested}) == []


def test_chrome_events_round_to_microseconds():
    obs.enable()
    with obs.span("x"):
        pass
    (ev,) = [e for e in chrome_trace_events(obs.tracer().spans)
             if e["ph"] == "X"]
    assert ev["ts"] >= 0 and ev["dur"] >= 0
    assert ev["pid"] == 0 and ev["tid"] == 0


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    c = metrics.counter("c")
    c.add()
    c.add(2.5)
    assert c.value == 3.5
    g = metrics.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3 and g.max == 7
    h = metrics.histogram("h")
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    assert h.count == 4
    assert h.mean == pytest.approx(0.02675)
    assert h.min == 0.001 and h.max == 0.1
    assert 0 < h.percentile(50) <= h.percentile(99) <= h.max
    snap = metrics.snapshot()
    assert snap["c"]["value"] == 3.5
    assert snap["g"]["max"] == 7
    assert snap["h"]["count"] == 4


def test_metric_type_conflict_raises():
    metrics.counter("m")
    with pytest.raises(TypeError):
        metrics.gauge("m")


def test_histogram_thread_safety():
    h = metrics.histogram("hts")
    n, workers = 5000, 8

    def work():
        for _ in range(n):
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n * workers
    assert sum(h.buckets) == n * workers


# ----------------------------------------------------------------------
# compile_stats thread safety + seconds attribution
# ----------------------------------------------------------------------
def test_compile_stats_concurrent_records_are_exact():
    with compile_stats.track() as st:
        n, workers = 2000, 8

        def work():
            for _ in range(n):
                compile_stats.record_batched_evals(1, shared=True)
                compile_stats.record_compile("t")
                compile_stats.record_eval_seconds(0.001)

        threads = [threading.Thread(target=work)
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert st.batched_evals == st.shared_evals == n * workers
    assert st.compiles == n * workers
    assert st.compiles_by_kind["t"] == n * workers
    assert st.eval_seconds == pytest.approx(0.001 * n * workers)


def test_compile_stats_seconds_ride_the_delta():
    with compile_stats.track() as outer:
        compile_stats.record_compile_seconds(1.5)
        with compile_stats.track() as inner:
            compile_stats.record_compile_seconds(0.25)
            compile_stats.record_eval_seconds(0.5)
    assert inner.compile_seconds == pytest.approx(0.25)
    assert inner.eval_seconds == pytest.approx(0.5)
    assert outer.compile_seconds == pytest.approx(1.75)
    d = outer.as_dict()
    assert d["compile_seconds"] == pytest.approx(1.75)


# ----------------------------------------------------------------------
# engine attribution: compile spans == compile_stats.compiles
# ----------------------------------------------------------------------
def test_engine_compile_and_eval_spans_match_stats():
    from repro.core import Sparseloop, matmul
    from repro.core.presets import bitmask_design, two_level_arch
    from repro.core.vmapper import SPMSPM_TEMPLATE

    from repro.core.batched import clear_caches
    clear_caches()                   # force a fresh compile
    tr = obs.enable()
    design = bitmask_design(two_level_arch())
    wl = matmul(16, 16, 16, densities={"A": ("uniform", 0.5),
                                       "B": ("uniform", 0.5)})
    model = Sparseloop(design)
    bm = model.batched_model(wl, SPMSPM_TEMPLATE,
                             check_capacity=False)
    bounds = np.asarray([[2, 2, 2, 4, 16, 8]] * 4)
    with compile_stats.track() as st:
        r1 = bm.evaluate(bounds)
        r2 = bm.evaluate(bounds)          # warm: same shape
    assert np.allclose(r1["edp"], r2["edp"])
    compile_spans = tr.find("engine.compile")
    eval_spans = tr.find("engine.eval")
    assert len(compile_spans) == st.compiles
    assert len(eval_spans) >= 1
    assert st.compile_seconds > 0
    assert st.eval_seconds > 0
    assert sum(s.dur for s in compile_spans) <= \
        st.compile_seconds + 1e-6
    span = compile_spans[0]
    assert span.attrs["kind"] == "template"
    assert span.attrs["candidates"] == 4


# ----------------------------------------------------------------------
# SearchLog timing contract
# ----------------------------------------------------------------------
def test_generation_record_back_compat_from_dict():
    from repro.search.log import GenerationRecord, SearchLog
    old = {"strategy": "es", "metric": "edp",
           "records": [{"generation": 0, "evaluations": 8, "valid": 4,
                        "best_fitness": 1.0, "best_cycles": 2.0,
                        "best_energy_pj": 3.0, "best_edp": 1.0}]}
    log = SearchLog.from_dict(old)
    assert log.records[0].wall_time_s == 0.0
    assert log.timing == {}
    # unknown future keys are ignored, not fatal
    rec = GenerationRecord.from_dict(
        dict(old["records"][0], wall_time_s=0.5, future_field=1))
    assert rec.wall_time_s == 0.5


def test_searchlog_timing_split_and_roundtrip(tmp_path):
    from repro.search.log import GenerationRecord, SearchLog
    log = SearchLog(strategy="es", metric="edp", seed=3)
    log.append(GenerationRecord(0, 8, 4, 1.0, 2.0, 3.0, 1.0,
                                wall_time_s=0.125))
    log.timing = {"wall_s": 0.5, "compile_s": 0.25, "eval_s": 0.125,
                  "compiles": 1}
    full = json.loads(log.to_json())
    assert full["timing"]["compile_s"] == 0.25
    assert full["records"][0]["wall_time_s"] == 0.125
    stripped = json.loads(log.to_json(timing=False))
    assert "timing" not in stripped
    assert "wall_time_s" not in stripped["records"][0]
    assert log.wall_time_s == pytest.approx(0.125)
    # save/load roundtrip keeps the timing fields
    path = tmp_path / "log.json"
    log.save(path)
    back = SearchLog.load(path)
    assert back.to_json() == log.to_json()
    assert not (tmp_path / "log.json.tmp").exists()


def test_searchlog_save_is_atomic_replace(tmp_path, monkeypatch):
    """A crash mid-write must never leave a truncated log at the final
    path: the write goes to a temp file first."""
    from repro.search.log import GenerationRecord, SearchLog
    log = SearchLog(strategy="es", metric="edp")
    log.append(GenerationRecord(0, 8, 4, 1.0, 2.0, 3.0, 1.0))
    path = tmp_path / "log.json"
    log.save(path)
    good = path.read_text()

    import os as _os
    def boom(src, dst):
        raise OSError("simulated crash before replace")
    monkeypatch.setattr(_os, "replace", boom)
    log2 = SearchLog(strategy="anneal", metric="cycles")
    with pytest.raises(OSError):
        log2.save(path)
    assert path.read_text() == good   # old content intact


# ----------------------------------------------------------------------
# the profiler's clock: spans as TraceAnnotations in a jax.profiler trace
# ----------------------------------------------------------------------
@pytest.fixture
def fresh_programs():
    """Programs compiled here are dropped afterwards: later tests in the
    process count the compiles they make."""
    from repro.core.batched import clear_caches
    clear_caches()
    yield
    clear_caches()


def _small_search_and_sweep():
    """A small fused search, then the winner through the network path
    (one single-candidate call, as the fleet sweep makes)."""
    from repro.core import Sparseloop, matmul
    from repro.core.mapper import MapspaceConstraints
    from repro.core.presets import coordinate_list_design, two_level_arch
    from repro.search import run_search
    wl = matmul(32, 32, 32, densities={"A": ("uniform", 0.3),
                                       "B": ("uniform", 0.3)})
    design = coordinate_list_design(two_level_arch(buffer_kwords=8))
    cons = MapspaceConstraints(budget=96, seed=0, spatial={1: {"n": 4}})
    res = run_search(design, wl, cons, strategy="es", key=3, mesh=None,
                     fused=True)
    out = Sparseloop(design).evaluate_network([wl], [[res.best_nest]])
    return res, out


def _inside(events, child: str, parent: str) -> list[bool]:
    """For each ``child`` event, whether some ``parent`` event on the
    same clock contains it."""
    outer = [(s, e) for n, s, e in events if n == parent]
    return [any(ps <= s and e <= pe for ps, pe in outer)
            for n, s, e in events if n == child]


def test_spans_nest_on_the_profiler_clock(tmp_path, fresh_programs):
    import jax
    from jax.profiler import ProfileData
    _small_search_and_sweep()            # compiles stay out of the trace
    tr = obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        res, out = _small_search_and_sweep()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    prog = ("search.", "engine.")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(prog)]
    names = [n for n, _, _ in events]
    # every span of the tracer is on the profiler's host plane, once
    for name in {s.name for s in tr.spans}:
        assert names.count(name) == len(tr.find(name)), name
    for child in ("search.prepare", "search.chunk", "search.absorb",
                  "search.validate"):
        assert names.count(child) >= 1
        assert all(_inside(events, child, "search.run")), child
    assert all(_inside(events, "search.absorb", "search.chunk"))
    for child in ("engine.dispatch", "engine.fetch"):
        assert all(_inside(events, child, "engine.eval")), child
    # the network call: dispatch and fetch inside engine.eval inside
    # engine.batch
    assert any(_inside(events, "engine.eval", "engine.batch"))
    (batch,) = [(s, e) for n, s, e in events if n == "engine.batch"]
    assert {n for n, s, e in events if batch[0] <= s and e <= batch[1]} \
        == {"engine.batch", "engine.eval", "engine.dispatch",
            "engine.fetch"}
    # one clock: the search's phases follow each other in order
    first = {n: min(s for m, s, _ in events if m == n)
             for n in ("search.prepare", "search.chunk", "search.validate")}
    assert first["search.prepare"] < first["search.chunk"] \
        < first["search.validate"]
    # the attributes stay in the obs record
    (val,) = tr.find("search.validate")
    assert val.attrs["walked"] >= 1
    (bsp,) = tr.find("engine.batch")
    assert bsp.attrs == {"candidates": 1, "groups": 1}
    assert res.log.timing["prepare_s"] > 0
    assert res.log.timing["validate_s"] > 0
    assert np.isfinite(out[0]["edp"]).all()


def test_disabled_tracer_builds_no_annotation(monkeypatch, fresh_programs):
    import jax

    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    obs.enable()                         # the patch is what a span uses
    with pytest.raises(AssertionError):
        with obs.span("x"):
            pass
    assert obs.tracer().spans == []
    obs.disable()
    res, _ = _small_search_and_sweep()   # off: no annotation anywhere
    assert res.best is not None
