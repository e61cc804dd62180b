"""Search loop: milliseconds per generation, over every search of the
window (the fused chunks' wall, or the host loop's per-generation wall,
divided by the generations they ran)."""


def read(records):
    searches = records.get("searches") or []
    gens = sum(s["generations"] for s in searches)
    if not gens:
        return None
    return 1e3 * sum(s["loop_s"] for s in searches) / gens
