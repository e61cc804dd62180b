"""Fleet extraction: the ``fleet.extract`` span, milliseconds per sweep of
the window."""


def read(records):
    spans = [s for s in records.get("spans") or []
             if s.name == "fleet.extract"]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
