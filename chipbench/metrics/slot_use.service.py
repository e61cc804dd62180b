"""The batcher: live candidates as a share of the slots invoked (live
plus padding) over the window's ``dse.batch`` spans, in percent."""


def read(records):
    spans = [s for s in records.get("spans") or [] if s.name == "dse.batch"]
    live = sum(s.attrs.get("candidates", 0) for s in spans)
    slots = live + sum(s.attrs.get("padded", 0) for s in spans)
    if not slots:
        return None
    return 100.0 * live / slots
