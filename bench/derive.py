"""What one run recorded, and the derivations that several metrics share.

Every metric is a reader ``metrics/<name>.py`` with ``read(run)``, which
returns a number or ``None`` where the run holds nothing to read; the
harness then leaves the metric out of the line.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench import counts
from bench import trace as tr


@dataclasses.dataclass
class Run:
    cfg: dict
    loop: str                   # open | closed
    seconds: float
    t_open: float               # perf_counter at window open
    close: float
    setup_s: float
    reqs: list                  # requests sent in the window (harness.Req)
    steps: list                 # worker steps in the window (harness.Step)
    peaks: dict
    compile_s: float            # backend compile seconds in set-up
    end: float = 0.0            # perf_counter when serving stopped
    trace: Optional[tr.Trace] = None   # clipped to the window, host clock
    window_s: float = 0.0              # length of the traced window

    def tokens_in_window(self) -> list:
        return [t for r in self.reqs for t in r.token_t
                if self.t_open <= t <= self.close]


def prefill_flops(run: Run) -> float:
    return float(sum(counts.prefill_flops(run.cfg, n)
                     for s in run.steps for n in s.prefills))


def decode_steps(run: Run) -> list:
    """Keys seen per active slot, one list per decode call in the window.
    A worker step decodes once per instance with an active slot."""
    return [s.decoded for s in run.steps if s.decoded]


def useful_flops(run: Run) -> float:
    return prefill_flops(run) + float(
        sum(counts.decode_flops(run.cfg, d) for d in decode_steps(run)))


def mfu(run: Run) -> Optional[float]:
    """Useful operations of the window's prefills and decode steps over the
    traced window, as a share of the chip's peak (%)."""
    if run.trace is None or run.window_s <= 0:
        return None
    return 100.0 * useful_flops(run) / run.window_s / run.peaks["bf16_flop_per_s"]


def decode_roofline(run: Run) -> Optional[float]:
    """Least time of the window's decode steps by their operations or bytes,
    whichever bounds each, over their device time (%)."""
    if run.trace is None:
        return None
    secs, n = tr.program_time(run.trace, "jit_decode_step")
    steps = decode_steps(run)
    if n == 0 or n != len(steps):
        return None
    least = sum(max(counts.decode_flops(run.cfg, d) / run.peaks["bf16_flop_per_s"],
                    counts.decode_bytes(run.cfg, d) / run.peaks["hbm_bytes_per_s"])
                for d in steps)
    return 100.0 * least / secs


def idle_share(run: Run) -> Optional[float]:
    """Share of the traced window with no operation on the device (%)."""
    if run.trace is None or run.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy(run.trace) / run.window_s)


def program_gap_ms(run: Run) -> Optional[float]:
    g = tr.program_gaps(run.trace) if run.trace is not None else []
    return 1e3 * float(np.mean(g)) if g else None


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None
