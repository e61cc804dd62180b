"""The device: the share of the traced window in which no operation ran
on it, in percent (``chipbench/trace_reduce.py``)."""


def read(records):
    trace = records.get("trace") or {}
    if not trace.get("devices"):
        return None
    return trace.get("idle_pct")
