"""Share of the schedule cache's per-image lookups in the window that
hit (the engine's ``image_hits / image_lookups`` counters)."""


def read(w):
    hits, lookups = w.cache
    if not lookups:
        return None
    return hits / lookups
