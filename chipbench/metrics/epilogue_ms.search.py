"""Entry and validation of a search: its wall time less its loop's own
time (``run_search`` set-up, the archive, the scalar-oracle walk over the
winners), milliseconds per search."""


def read(records):
    searches = records.get("searches") or []
    if not searches:
        return None
    return 1e3 * sum(s["wall_s"] - s["loop_s"]
                     for s in searches) / len(searches)
