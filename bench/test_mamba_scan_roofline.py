"""``mamba_scan_roofline``: the fused scan's bytes by hand, the kernel's
events found inside the prefill programs of a trace, and the reader on a
synthetic run and on the recorded v5e traces, which hold no such kernel."""
import json
from pathlib import Path

import pytest

from bench import derive, harness, kernel_trace, run
from bench import trace as tr
from bench.test_engine_spans import _laid_out

HERE = Path(__file__).resolve().parent
PEAKS = {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = ["v5e_code_completion.xplane.pb.gz", "v5e_engine_spans.xplane.pb.gz"]


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _module():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mamba_scan_roofline", HERE / "metrics" / "mamba_scan_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader():
    return run.reader("mamba_scan_roofline")


def test_scan_bytes_by_hand():
    c = _config("falcon_mamba7b")
    L, DI, N, n = 48, 8192, 16, 512
    per_layer = (n * DI * 4              # dt, f32
                 + n * DI * 2            # x, bf16
                 + 2 * n * N * 4         # B and C, f32
                 + DI * N * 4 + DI * 2   # A (f32) and D (bf16)
                 + n * DI * 4            # y, f32
                 + DI * N * 4)           # the final state, f32
    assert per_layer == 43_073_536
    scan_bytes = _module().scan_bytes
    assert scan_bytes(c, n) == L * per_layer
    # about 2.5 ms of HBM traffic for a 512-token prompt, against the 13.7 GB
    # a layer that the chunked XLA scan moved
    assert scan_bytes(c, n) / 819e9 == pytest.approx(2.524e-3, rel=1e-3)


def test_instruction_names():
    assert kernel_trace.instruction(
        '%mamba_scan.1 = (f32[1,512,8192]{2,1,0:T(8,128)}) custom-call(%a), '
        'custom_call_target="tpu_custom_call"') == "mamba_scan"
    assert kernel_trace.instruction("%mamba_scan = f32[8] custom-call()") == "mamba_scan"
    assert kernel_trace.instruction("%fusion.149 = bf16[8] fusion(%x)") == "fusion"
    assert kernel_trace.instruction("%mamba_scan_ref.2 = f32[8] fusion()") == "mamba_scan_ref"


def test_time_inside_counts_only_the_named_programs_in_the_window():
    programs = (("jit_prefill", 0.0, 2.0), ("jit_decode_step", 3.0, 4.0),
                ("jit_prefill", 5.0, 7.0))
    ops = ((0.5, 1.0), (1.5, 1.75), (3.0, 3.5), (4.5, 4.75), (6.0, 6.5))
    # the decode step's kernel and the one between programs do not count
    assert kernel_trace.time_inside(ops, programs, "jit_prefill", 0.0, 8.0) == \
        pytest.approx((1.25, 3))
    # clipped to the window: the first op half, the last outside
    assert kernel_trace.time_inside(ops, programs, "jit_prefill", 0.75, 5.5) == \
        pytest.approx((0.5, 2))
    assert kernel_trace.time_inside((), programs, "jit_prefill", 0.0, 8.0) == (0.0, 0)


def _run_with(cfg, lengths):
    """A run whose window holds one prefill program per prompt."""
    progs, t = [], 0.0
    for _ in lengths:
        progs.append(("jit_prefill", t, t + 1.0))
        t += 1.001
    r = derive.Run(cfg=cfg, loop="closed", seconds=t, t_open=0.0, close=t,
                   setup_s=0.0, reqs=[], steps=[harness.Step(prefills=[n]) for n in lengths],
                   peaks=PEAKS, compile_s=0.0)
    r.trace, r.window_s = tr.Trace(ops=[(s, e) for _, s, e in progs], programs=progs,
                                   spans=[("bench.window", 0.0, t)]), t
    return r


def test_reader_on_a_synthetic_run(monkeypatch):
    c, lengths = _config("falcon_mamba7b"), [128, 512, 2048]
    least = sum(_module().scan_bytes(c, n) for n in lengths) / 819e9
    monkeypatch.setattr(kernel_trace, "of", lambda *a, **k: (least, 48 * 3))
    read = _reader()
    # a kernel at its roofline reads 100%, one twice as slow 50%
    assert read(_run_with(c, lengths)) == pytest.approx(100.0)
    monkeypatch.setattr(kernel_trace, "of", lambda *a, **k: (2 * least, 48 * 3))
    assert read(_run_with(c, lengths)) == pytest.approx(50.0)
    # prefills not all counted, a dense model, or no kernel: nothing to read
    r = _run_with(c, lengths)
    r.steps = r.steps[:2]
    assert read(r) is None
    assert read(_run_with(_config("coder33b"), [512])) is None
    monkeypatch.setattr(kernel_trace, "of", lambda *a, **k: None)
    assert read(_run_with(c, lengths)) is None


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_v5e_traces(tmp_path, monkeypatch, name):
    """The programs this module reads are the ones ``trace.load`` reads, on
    the host's clock; the traces hold no Mamba kernel, so the reader has
    nothing to read there, as on a parent without the kernel; and the
    events of a kernel the prefill does hold (XLA's fusions) are found
    inside the prefill programs of the run's window."""
    path, clipped = _laid_out(tmp_path, name, "coder33b.code_completion")
    monkeypatch.setattr(run, "CACHE", tmp_path)
    ops, programs = kernel_trace.load(str(path), "mamba_scan")
    assert ops == () and list(programs) == tr.load(str(path)).programs
    rec = derive.Run(cfg=_config("falcon_mamba7b"), loop="closed", seconds=0.0,
                     t_open=0.0, close=0.0, setup_s=0.0, reqs=[], peaks=PEAKS,
                     compile_s=0.0, trace=clipped,
                     steps=[harness.Step(prefills=[512])] * tr.program_time(
                         clipped, "jit_prefill")[1])
    assert kernel_trace.of(rec, "mamba_scan", "jit_prefill") is None
    assert _reader()(rec) is None
    secs, n = kernel_trace.of(rec, "fusion", "jit_prefill")
    assert n > 0 and 0 < secs < tr.program_time(clipped, "jit_prefill")[0]
