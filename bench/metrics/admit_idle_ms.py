"""Device-idle milliseconds inside the engine's ``engine.admit`` spans in
the traced window, per such span: what one admission (padding, prefill and
its wait, cache insert, first-token pull, bookkeeping) leaves the device
waiting."""
from bench import engine_spans


def read(run):
    spans = engine_spans.of(run)
    if spans is None:
        return None
    secs, n = engine_spans.idle_inside(run.trace, spans, "engine.admit",
                                       *engine_spans.window(run.trace))
    return 1e3 * secs / n if n else None
