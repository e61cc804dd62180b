"""Per-shape dispatch in the sweep: compiled-program calls, counted as
``engine.dispatch`` spans, per ``fleet.sweep`` span of the window."""


def read(records):
    spans = records.get("spans") or []
    sweeps = sum(s.name == "fleet.sweep" for s in spans)
    calls = sum(s.name == "engine.dispatch" for s in spans)
    if not sweeps or not calls:
        return None
    return calls / sweeps
