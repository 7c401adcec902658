"""Device-idle milliseconds inside the engine's ``engine.decode`` spans in
the traced window, per such span: what one instance's decode call (token and
position upload, the program and its wait, the argmax pull and per-slot
feedback of ``engine.sample``) leaves the device waiting."""
from bench import engine_spans


def read(run):
    spans = engine_spans.of(run)
    if spans is None:
        return None
    secs, n = engine_spans.idle_inside(run.trace, spans, "engine.decode",
                                       *engine_spans.window(run.trace))
    return 1e3 * secs / n if n else None
