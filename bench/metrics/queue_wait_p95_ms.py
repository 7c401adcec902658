"""95th percentile, milliseconds, of how long the engine held a request in
its worker's queue: from the end of the request's ``engine.enqueue`` span to
the start of its ``engine.admit``, over the requests whose admission starts
in the traced window."""
from bench import engine_spans
from bench.derive import percentile


def read(run):
    spans = engine_spans.of(run)
    if spans is None:
        return None
    v = percentile(engine_spans.queue_waits(spans, *engine_spans.window(run.trace)), 95)
    return None if v is None else 1e3 * v
