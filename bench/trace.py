"""From a profiler trace to device busy time, idle gaps and per-program time.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` keeps four things of it, in seconds:

- ``ops``: every operation on the device ("XLA Ops" line of ``/device:TPU:0``);
- ``programs``: every program run on the device ("XLA Modules"), by name;
- ``spans``: the harness's own host spans, named ``bench.*``.

Device times are moved onto the host's clock by the device's offset: a
program cannot start before the host enqueued it (``DoEnqueueProgram``, same
``run_id``), so the offset is the least lead of a program's start over its
enqueue. Everything below works on those lists, on the host's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_PLANE = "/device:TPU:0"


@dataclasses.dataclass
class Trace:
    ops: list           # (start, end)
    programs: list      # (name, start, end)
    spans: list         # (name, start, end)


def find(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"want one xplane.pb under {log_dir}, found {files}")
    return files[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, mods, spans, enq = [], [], [], {}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name == DEVICE_PLANE and line.name == "XLA Ops":
                ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
            elif plane.name == DEVICE_PLANE and line.name == "XLA Modules":
                for e in line.events:
                    run = dict(e.stats).get("run_id")
                    mods.append((e.name.split("(")[0], e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9, run))
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
                    elif e.name == "DoEnqueueProgram":
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            enq.setdefault(run, e.start_ns * 1e-9)
    leads = [s - enq[r] for _, s, _, r in mods if r in enq]
    offset = min(leads) if leads else 0.0
    return Trace(
        ops=sorted((s - offset, e - offset) for s, e in ops),
        programs=sorted(((n, s - offset, e - offset) for n, s, e, _ in mods),
                        key=lambda p: p[1]),
        spans=sorted(spans, key=lambda s: s[1]))


def clip(tr: Trace, t0: float, t1: float) -> Trace:
    """The part of the trace inside [t0, t1]."""
    def cut(s, e):
        return max(s, t0), min(e, t1)
    return Trace(
        ops=[cut(s, e) for s, e in tr.ops if e > t0 and s < t1],
        programs=[(n, *cut(s, e)) for n, s, e in tr.programs if e > t0 and s < t1],
        spans=[(n, *cut(s, e)) for n, s, e in tr.spans if e > t0 and s < t1])


def union(intervals) -> list:
    """Disjoint, sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy(tr: Trace) -> float:
    """Seconds in which an operation ran on the device."""
    return sum(e - s for s, e in union(tr.ops))


def gaps(tr: Trace, t0: float, t1: float) -> list:
    """Idle intervals of the device inside [t0, t1]."""
    out, t = [], t0
    for s, e in union(tr.ops):
        if s > t:
            out.append((t, min(s, t1)))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return [(s, e) for s, e in out if e > s]


def program_time(tr: Trace, prefix: str) -> tuple:
    """Device seconds and count of the programs whose name starts with
    ``prefix`` (``jit_prefill``, ``jit_decode_step``)."""
    hits = [e - s for n, s, e in tr.programs if n.startswith(prefix)]
    return sum(hits), len(hits)


def program_gaps(tr: Trace) -> list:
    """Idle seconds between consecutive device programs."""
    p = tr.programs
    return [max(b[1] - a[2], 0.0) for a, b in zip(p, p[1:])]


def innermost(spans, times) -> list:
    """Name of the innermost span open at each of the sorted ``times``
    (host spans of one thread nest), by one sweep over the spans."""
    out, open_, i = [], [], 0
    spans = sorted(spans, key=lambda sp: sp[1])
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[2] > t]
        out.append(open_[-1][0] if open_ else "no harness span")
    return out


def time_under(tr: Trace, span: str) -> tuple:
    """Device seconds of the programs that start inside a host span named
    ``span`` (such spans do not overlap), and the number of such spans."""
    under = sorted((s, e) for n, s, e in tr.spans if n == span)
    starts = [s for s, _ in under]
    t = 0.0
    for _, s, e in tr.programs:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < under[k][1]:
            t += e - s
    return t, len(under)


def idle_by_span(tr: Trace, t0: float, t1: float) -> dict:
    """Idle seconds of the device inside [t0, t1], by the innermost harness
    span at the middle of each gap."""
    out: dict = {}
    g = gaps(tr, t0, t1)
    for (s, e), name in zip(g, innermost(tr.spans, [0.5 * (s + e) for s, e in g])):
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def top_programs(tr: Trace, k: int = 10) -> list:
    """Device seconds by program name, most first."""
    out: dict = {}
    for n, s, e in tr.programs:
        out[n] = out.get(n, 0.0) + (e - s)
    return sorted(out.items(), key=lambda kv: -kv[1])[:k]
