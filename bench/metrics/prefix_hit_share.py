"""Share of full prompt pages the prefix index served from resident pages
(``PagedKVStore`` hits over hits plus misses) in the window."""


def read(record):
    hits = record.counters.get("prefix_hits", 0)
    total = hits + record.counters.get("prefix_misses", 0)
    if not total:
        return None
    return 100.0 * hits / total
