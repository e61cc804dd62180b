"""Winner validation: the scalar-oracle walk over the archive, which
``run_search`` times around its ``search.validate`` span
(``SearchLog.timing["validate_s"]``), milliseconds per search."""


def read(records):
    logs = [s["result"].log for s in records.get("searches") or []]
    times = [log.timing.get("validate_s") for log in logs]
    if not times or None in times:
        return None
    return 1e3 * sum(times) / len(times)
