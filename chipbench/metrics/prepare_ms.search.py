"""Search entry: the host's steps before the first chunk (encoding,
evaluator, bucket facade, fused-program lookup, initial carry), which
``run_search`` times around its ``search.prepare`` spans
(``SearchLog.timing["prepare_s"]``), milliseconds per search."""


def read(records):
    logs = [s["result"].log for s in records.get("searches") or []]
    times = [log.timing.get("prepare_s") for log in logs]
    if not times or None in times:
        return None
    return 1e3 * sum(times) / len(times)
