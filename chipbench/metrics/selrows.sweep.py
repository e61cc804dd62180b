"""Selected rows of a DSA sweep: rows evaluated with a selection (the
``selected`` attribute of the ``fleet.option`` spans, summed over the
options), per ``fleet.sweep`` span of the window."""


def read(records):
    spans = records.get("spans") or []
    sweeps = sum(s.name == "fleet.sweep" for s in spans)
    counted = [s.attrs["selected"] for s in spans
               if s.name == "fleet.option" and "selected" in s.attrs]
    if not sweeps or not counted:
        return None
    return sum(counted) / sweeps
