"""95th percentile, milliseconds, of how late each request of an open loop
was sent: the synchronous worker step holds the host while requests come
due."""
from bench.derive import percentile


def read(run):
    if run.loop != "open":
        return None
    v = percentile([r.submit_t - r.due for r in run.reqs], 95)
    return None if v is None else 1e3 * v
