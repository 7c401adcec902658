"""95th percentile, milliseconds, of the gaps between consecutive output
tokens of a request, over every gap that ends in the window."""
from bench.derive import percentile


def read(run):
    gaps = [b - a for r in run.reqs for a, b in zip(r.token_t, r.token_t[1:])
            if run.t_open <= b <= run.close]
    v = percentile(gaps, 95)
    return None if v is None else 1e3 * v
