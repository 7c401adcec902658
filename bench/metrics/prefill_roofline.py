"""Share of the roofline of the window's prefills (%): the least time each
prefill could take, by its operations or by its bytes (every weight once,
the slot's state written once, the logits), whichever bounds it, over the
device time of the prefill programs. Only a family that counts a prefill's
bytes (``prefill_bytes``) has a reading."""
from bench import counts, trace
from bench.families import family


def read(run):
    fam = family(run.cfg)
    if run.trace is None or not hasattr(fam, "prefill_bytes"):
        return None
    secs, n = trace.program_time(run.trace, "jit_prefill")
    lengths = [p for s in run.steps for p in s.prefills]
    if n == 0 or n != len(lengths):
        return None
    least = sum(max(counts.prefill_flops(run.cfg, p) / run.peaks["bf16_flop_per_s"],
                    fam.prefill_bytes(run.cfg, p) / run.peaks["hbm_bytes_per_s"])
                for p in lengths)
    return 100.0 * least / secs
