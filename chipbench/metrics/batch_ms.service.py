"""The service's evaluator thread: mean milliseconds of one program
invocation (``dse.batch`` span) over the window."""


def read(records):
    spans = [s for s in records.get("spans") or [] if s.name == "dse.batch"]
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in spans) / len(spans)
