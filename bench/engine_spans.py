"""The serving engine's own host spans in a profiler trace, and the device's
idle time inside them.

The engine marks its layers with ``jax.profiler.TraceAnnotation`` spans
named ``engine.*`` (``serving/engine.py``, ``serving/kv_cache.py``); the
spans of one request carry its ``rid``. :func:`load` reads them from an
``.xplane.pb`` as ``(name, start, end, attrs)``, in seconds on the host's
clock: the clock of :mod:`bench.trace`'s harness spans and, after its
offset, of its device programs. A program that emits no such span reads no
spans, and each reader built on this module then finds nothing to read.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from typing import Optional

from bench import trace as tr

PREFIX = "engine."
WINDOW = "bench.window"


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != WINDOW and not e.name.startswith(PREFIX):
                    continue
                start, end = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if e.name == WINDOW:
                    window = (start, end)
                else:
                    spans.append((e.name, start, end, dict(e.stats)))
    # outer first where two spans start together, so the later one nests
    return window, tuple(sorted(spans, key=lambda sp: (sp[1], -sp[2])))


def load(path: str) -> tuple:
    """``(window, spans)`` of a trace file: the harness's ``bench.window``
    as ``(start, end)`` (``None`` where it has none), and every
    ``engine.*`` span, sorted by start."""
    return _load(path, os.stat(path).st_mtime_ns)


def window(trace: tr.Trace) -> Optional[tuple]:
    """``(t0, t1)`` of the harness's window in a trace clipped to it."""
    win = [(s, e) for n, s, e in trace.spans if n == WINDOW]
    return win[0] if win else None


def of(run, root: Optional[str] = None) -> Optional[tuple]:
    """The engine spans of a traced run, or ``None`` where it has none.

    A run's record keeps its clipped trace but not the file it came from:
    the file is the newest under ``root`` (the harness's trace directory,
    ``.bench_cache/trace``) whose ``bench.window`` is the run's window."""
    if run.trace is None or window(run.trace) is None:
        return None
    if root is None:
        from bench import run as bench_run
        root = str(bench_run.CACHE / "trace")
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        win, spans = load(path)
        if win == window(run.trace):
            return spans or None
    return None


def idle_inside(trace: tr.Trace, spans, name: str, t0: float, t1: float) -> tuple:
    """Device-idle seconds inside the spans called ``name`` (spans of one
    name do not overlap) within [t0, t1], and the number of such spans."""
    gaps = tr.gaps(trace, t0, t1)
    starts = [s for s, _ in gaps]
    secs, n = 0.0, 0
    for sp_name, s, e, _ in spans:
        if sp_name != name or e <= t0 or s >= t1:
            continue
        s, e, n = max(s, t0), min(e, t1), n + 1
        k = max(bisect.bisect_right(starts, s) - 1, 0)
        while k < len(gaps) and gaps[k][0] < e:
            secs += max(0.0, min(e, gaps[k][1]) - max(s, gaps[k][0]))
            k += 1
    return secs, n


def idle_by_innermost(trace: tr.Trace, spans, t0: float, t1: float,
                      under: Optional[set] = None) -> dict:
    """Device-idle seconds inside [t0, t1] by the innermost engine span at
    the middle of each gap, the rule of ``trace.idle_by_span``. With
    ``under``, only the gaps whose innermost harness span is one of those
    names (``{"bench.step", "bench.admit"}``)."""
    gaps = tr.gaps(trace, t0, t1)
    mids = [0.5 * (s + e) for s, e in gaps]
    engine = tr.innermost([sp[:3] for sp in spans], mids)
    harness = tr.innermost(trace.spans, mids)
    out: dict = {}
    for (s, e), name, outer in zip(gaps, engine, harness):
        if under is not None and outer not in under:
            continue
        name = name if name.startswith(PREFIX) else "no engine span"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def queue_waits(spans, t0: float, t1: float) -> list:
    """For each request whose ``engine.admit`` starts in [t0, t1], seconds
    from the end of its ``engine.enqueue`` to that start."""
    enqueued = {a["rid"]: e for n, _, e, a in spans if n == "engine.enqueue"}
    return [s - enqueued[a["rid"]] for n, s, _, a in spans
            if n == "engine.admit" and t0 <= s <= t1 and a.get("rid") in enqueued]
