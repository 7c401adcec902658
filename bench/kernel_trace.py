"""A Pallas kernel's device time inside one kind of program, from the file of
a traced run.

:mod:`bench.trace` keeps the device's operations as bare intervals; a
kernel's share of its roofline needs them by name. On the v5e the events of
the device's "XLA Ops" line are named by the text of their HLO instruction,
and a ``pallas_call`` named ``<kernel>`` is the instruction
``%<kernel> = ... custom-call(...)`` or ``%<kernel>.<n> = ...``. :func:`load`
reads those events and the device's programs from an ``.xplane.pb`` and
moves them onto the host's clock by :mod:`bench.trace`'s rule (the least
lead of a program's start over its ``DoEnqueueProgram``); :func:`of` finds a
run's file and sums its kernel's events inside the window's programs.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Optional

from bench import engine_spans
from bench import trace as tr


def instruction(event_name: str) -> str:
    """The HLO instruction an "XLA Ops" event names, without its ``%`` and
    its ``.<n>`` suffix: ``mamba_scan`` for ``%mamba_scan.3 = (...) ...``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, kernel: str) -> tuple:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, mods, enq = [], [], {}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name == tr.DEVICE_PLANE and line.name == "XLA Ops":
                ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events if instruction(e.name) == kernel]
            elif plane.name == tr.DEVICE_PLANE and line.name == "XLA Modules":
                for e in line.events:
                    mods.append((e.name.split("(")[0], e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9,
                                 dict(e.stats).get("run_id")))
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name == "DoEnqueueProgram":
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            enq.setdefault(run, e.start_ns * 1e-9)
    leads = [s - enq[r] for _, s, _, r in mods if r in enq]
    offset = min(leads) if leads else 0.0
    return (tuple(sorted((s - offset, e - offset) for s, e in ops)),
            tuple(sorted(((n, s - offset, e - offset) for n, s, e, _ in mods),
                         key=lambda p: p[1])))


def load(path: str, kernel: str) -> tuple:
    """``(ops, programs)`` of a trace file on the host's clock: the
    ``(start, end)`` of every event of ``kernel``, and every device program
    as ``(name, start, end)``, each sorted by start."""
    return _load(path, os.stat(path).st_mtime_ns, kernel)


def time_inside(ops, programs, prefix: str, t0: float, t1: float) -> tuple:
    """Seconds of the ``ops`` that lie inside a program whose name starts
    with ``prefix``, clipped to [t0, t1], and their number."""
    starts = [s for _, s, _ in programs]
    secs, n = 0.0, 0
    for s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or not programs[k][0].startswith(prefix) or e > programs[k][2]:
            continue
        s, e = max(s, t0), min(e, t1)
        if e > s:
            secs, n = secs + (e - s), n + 1
    return secs, n


def of(run, kernel: str, prefix: str, root: Optional[str] = None) -> Optional[tuple]:
    """``(seconds, count)`` of ``kernel``'s events inside the programs named
    ``prefix`` in a traced run's window, or ``None`` where it has none.

    The file is the newest under ``root`` (the harness's trace directory)
    whose ``bench.window`` is the run's, as :func:`engine_spans.of` finds it."""
    win = None if run.trace is None else engine_spans.window(run.trace)
    if win is None:
        return None
    if root is None:
        from bench import run as bench_run
        root = str(bench_run.CACHE / "trace")
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        if engine_spans.load(path)[0] != win:
            continue
        secs, n = time_inside(*load(path, kernel), prefix, *win)
        return (secs, n) if n else None
    return None
