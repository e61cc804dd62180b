"""Per-shape dispatch in the sweep: its own evaluation seconds
(``FleetReport.eval_seconds``), milliseconds per sweep of the window."""


def read(records):
    sweeps = records.get("sweeps") or []
    if not sweeps:
        return None
    return 1e3 * sum(s["eval_s"] for s in sweeps) / len(sweeps)
