"""Operations of the real prompt tokens prefilled in the window over the
device time of the prefill programs, as a share of the chip's peak (%).
Padding to a bucket shows as waste."""
from bench import derive, trace


def read(run):
    if run.trace is None:
        return None
    secs, n = trace.program_time(run.trace, "jit_prefill")
    if n == 0 or n != sum(len(s.prefills) for s in run.steps):
        return None
    return 100.0 * derive.prefill_flops(run) / secs / run.peaks["bf16_flop_per_s"]
