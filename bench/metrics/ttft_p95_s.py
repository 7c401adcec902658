"""95th percentile of time to first token, seconds, over every request due
in the window of an open loop: from when it was due (not when it was sent) to
when the harness saw its first token. One that never got it counts as
waiting until serving stopped."""
from bench.derive import percentile


def read(run):
    if run.loop != "open":
        return None
    return percentile([(r.token_t[0] if r.token_t else run.end) - r.due
                       for r in run.reqs], 95)
