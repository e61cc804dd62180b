"""Per-shape lowering in the sweep: the self time of the ``engine.batch``
spans (each less its direct children, found by their ``parent`` links:
the compiled call's ``engine.eval``), milliseconds per ``fleet.sweep``
span of the window."""


def read(records):
    spans = records.get("spans") or []
    sweeps = sum(s.name == "fleet.sweep" for s in spans)
    batches = [s for s in spans if s.name == "engine.batch"]
    if not sweeps or not batches:
        return None
    ids = {s.sid for s in batches}
    children = sum(s.dur for s in spans if s.parent in ids)
    return 1e3 * (sum(s.dur for s in batches) - children) / sweeps
