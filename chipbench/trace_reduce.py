"""Reduce a profiler trace of one window to the device's busy time, its
idle share, its top operations and its longest idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
intervals (nanoseconds, one clock for host and device):

* per device plane (``/device:TPU:<i>``), the events of its op line
  (``XLA Ops``; else its module line; else every line), each named by
  its HLO instruction;
* on the host, the benchmark's own ``jax.profiler.TraceAnnotation``
  spans, whose names start with ``bench.``.

``reduce`` then works on those intervals alone, so a test can feed it a
small recorded trace:

* busy: the union of a device's op intervals inside the window (the
  ``bench.window`` span), averaged over the devices that ran anything;
* idle share: 1 - busy / window, in percent;
* device ops: seconds per op name, summed over devices and divided by
  their number, the ten largest;
* idle gaps: each gap of device 0's busy union inside the window, named
  by the innermost ``bench.`` span around its midpoint (``bench.window``
  itself where the host was between calls), seconds summed per name, the
  ten largest.
"""
from __future__ import annotations

#: device op lines, most specific first
OP_LINES = ("XLA Ops", "XLA Modules")
WINDOW = "bench.window"
TOP = 10


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, planes = {}, [], {}
    for plane in pd.planes:
        planes[plane.name] = [line.name for line in plane.lines]
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            use = next(([lines[n]] for n in OP_LINES if n in lines),
                       list(lines.values()))
            devices[plane.name] = [
                (op_name(e.name), float(e.start_ns),
                 float(e.start_ns + e.duration_ns))
                for line in use for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [(e.name, float(e.start_ns),
                      float(e.start_ns + e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
    return {"devices": devices, "host": host, "planes": planes}


def op_name(name: str) -> str:
    """An op event's instruction name: its text up to `` = `` (op events
    carry the whole HLO instruction), without the leading ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _window(trace: dict) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    evs = [(s, e) for evs in trace["devices"].values() for _, s, e in evs]
    if not evs:
        return 0.0, 0.0
    return min(s for s, _ in evs), max(e for _, e in evs)


def _host_at(trace: dict, t: float) -> str:
    """The innermost benchmark span around time t."""
    around = [(e - s, n) for n, s, e in trace["host"] if s <= t < e]
    return min(around)[1] if around else "none"


def reduce(trace: dict) -> dict:
    lo, hi = _window(trace)
    window_ns = hi - lo
    used = {d: evs for d, evs in trace["devices"].items() if evs}
    busy = {d: union(((s, e) for _, s, e in evs), lo, hi)
            for d, evs in used.items()}
    busy_ns = [sum(e - s for s, e in b) for b in busy.values()]
    busy_s = (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0
    ops: dict[str, float] = {}
    for evs in used.values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d / 1e9 / len(used)
    gaps: dict[str, float] = {}
    if busy:
        first = busy[sorted(busy)[0]]
        edges = [lo] + [x for se in first for x in se] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                name = _host_at(trace, (s + e) / 2)
                gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    window_s = window_ns / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": (100.0 * (1.0 - busy_s / window_s)
                         if window_s > 0 else None),
            "devices": len(used),
            "device_ops": [[n, v] for n, v in top],
            "idle_gaps": [[n, v] for n, v in idle]}
