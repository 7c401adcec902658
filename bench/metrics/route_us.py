"""Mean host microseconds of ``Engine.submit`` (the router tree) per
request sent in the window."""


def read(run):
    return 1e6 * sum(r.route_s for r in run.reqs) / len(run.reqs) if run.reqs else None
