"""Program dispatch in the sweep: the ``engine.dispatch`` spans (the
jitted call up to its return, before the host waits on the device),
milliseconds per ``fleet.sweep`` span of the window."""


def read(records):
    spans = records.get("spans") or []
    sweeps = sum(s.name == "fleet.sweep" for s in spans)
    calls = [s for s in spans if s.name == "engine.dispatch"]
    if not sweeps or not calls:
        return None
    return 1e3 * sum(s.dur for s in calls) / sweeps
