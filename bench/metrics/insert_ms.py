"""Device milliseconds per admission of the programs launched inside
``SlotCache.admit`` (the cache insert), from the trace."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    secs, n = trace.time_under(run.trace, "bench.admit")
    return 1e3 * secs / n if n else None
