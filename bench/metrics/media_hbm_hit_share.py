"""Share of media links in the window served from the library's HBM tier
(``KVLibrary.stats()`` hit counters: HBM hits over all hits and misses)."""


def read(run):
    d = run.lib_delta
    if not d:
        return None
    total = sum(v for k, v in d.items())
    if total <= 0:
        return None
    return 100.0 * d.get("hbm", 0) / total
