"""The context crossover of a DSA sweep: the ``fleet.ctx_crossover``
span (opened in ``fleet_sweep``), milliseconds per ``fleet.sweep`` span
of the window."""


def read(records):
    spans = records.get("spans") or []
    sweeps = sum(s.name == "fleet.sweep" for s in spans)
    cross = [s for s in spans if s.name == "fleet.ctx_crossover"]
    if not sweeps or not cross:
        return None
    return 1e3 * sum(s.dur for s in cross) / sweeps
