"""Share of its roofline of the Mamba prefill's fused selective scan (%): the
least time of the kernel's calls in the window's prefills, its bytes over
the chip's HBM bandwidth, over the device time of its events (the Pallas
kernel ``mamba_scan`` inside ``jit_prefill`` programs).

The reading is bound by bytes: the state never leaves VMEM, so each layer of
each prefill reads dt, x, B, C, A and D and writes y and the final state
once (:func:`scan_bytes`). The kernel's own work is VPU and EUP work, for
which ``bench/peaks.json`` has no peak, so a kernel bound by that work reads
well under 100% by design. Only the ``mamba1`` family has a reading, and only
where its prefill runs the kernel (not the XLA scan that came before it).
"""
from bench import kernel_trace, trace

F32 = 4
BYTES = 2              # x and D come in the model's dtype, bfloat16


def scan_bytes(cfg: dict, n: int) -> int:
    """One prompt of ``n`` tokens through every layer's scan: dt [n, DI],
    B and C [n, N] and A [DI, N] read in float32, x [n, DI] and D [DI] in
    bfloat16, y [n, DI] and the final state [DI, N] written in float32."""
    L, DI, N = cfg["num_hidden_layers"], cfg["intermediate_size"], cfg["state_size"]
    return L * (n * DI * (F32 + BYTES + F32) + 2 * n * N * F32
                + 2 * DI * N * F32 + DI * BYTES)


def read(run):
    if run.trace is None or run.cfg.get("reference") != "mamba1":
        return None
    lengths = [p for s in run.steps for p in s.prefills]
    if trace.program_time(run.trace, "jit_prefill")[1] != len(lengths) or not lengths:
        return None
    got = kernel_trace.of(run, "mamba_scan", "jit_prefill")
    if got is None:
        return None
    least = sum(scan_bytes(run.cfg, p) for p in lengths) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / got[0]
