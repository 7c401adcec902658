"""The serving engine's own spans: what a traced run at test size records,
and the reduction from them to the device's idle time and the queue wait."""
import json
from pathlib import Path

import jax
import pytest

from bench import engine_spans, run, traffic
from bench import trace as tr

HERE = Path(__file__).resolve().parent
REQUEST_SPANS = {"engine.route", "engine.enqueue", "engine.admit", "engine.prefill",
                 "engine.insert", "engine.first_token", "engine.complete"}
ALL_SPANS = REQUEST_SPANS | {"engine.step", "engine.decode", "engine.sample"}
ADMIT_CHILDREN = ("engine.prefill", "engine.insert", "engine.first_token")


@pytest.fixture
def small():
    """Device busy in [0, 2] and [3, 4]; two admissions and one decode."""
    trace = tr.Trace(
        ops=[(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)],
        programs=[("jit_prefill", 0.0, 2.0), ("jit_decode_step", 3.0, 4.0)],
        spans=[("bench.window", 0.0, 5.0), ("bench.step", 0.0, 4.2)])
    spans = (("engine.enqueue", -0.5, -0.4, {"rid": 1}),
             ("engine.step", 0.0, 4.2, {}),
             ("engine.admit", 0.0, 2.5, {"rid": 1}),
             ("engine.prefill", 0.0, 2.1, {"rid": 1}),
             ("engine.enqueue", 1.0, 1.1, {"rid": 2}),
             ("engine.admit", 2.5, 2.8, {"rid": 2}),
             ("engine.decode", 2.8, 4.1, {}),
             ("engine.sample", 4.0, 4.1, {}))
    return trace, spans


def test_idle_inside_spans_of_one_name(small):
    trace, spans = small
    assert engine_spans.idle_inside(trace, spans, "engine.admit", 0.0, 5.0) == \
        pytest.approx((0.8, 2))
    assert engine_spans.idle_inside(trace, spans, "engine.decode", 0.0, 5.0) == \
        pytest.approx((0.3, 1))
    # clipped to the window: the first admission's idle lies outside
    assert engine_spans.idle_inside(trace, spans, "engine.admit", 2.6, 5.0) == \
        pytest.approx((0.2, 1))


def test_idle_by_innermost_engine_span(small):
    trace, spans = small
    # gaps [2, 3] (middle 2.5: the second admission) and [4, 5] (4.5: none)
    assert engine_spans.idle_by_innermost(trace, spans, 0.0, 5.0) == \
        pytest.approx({"engine.admit": 1.0, "no engine span": 1.0})
    assert engine_spans.idle_by_innermost(trace, spans, 0.0, 5.0,
                                          under={"bench.step"}) == \
        pytest.approx({"engine.admit": 1.0})


def test_queue_waits(small):
    _, spans = small
    assert engine_spans.queue_waits(spans, 0.0, 5.0) == pytest.approx([0.4, 1.4])
    assert engine_spans.queue_waits(spans, 1.0, 5.0) == pytest.approx([1.4])


def _laid_out(tmp_path, name, cell):
    """A recorded trace under ``tmp_path`` as the harness lays one out, and
    its window."""
    import gzip
    import shutil
    dst = tmp_path / "trace" / cell / "plugins" / "profile" / "1" / "host.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(HERE / "testdata" / name) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    full = tr.load(str(dst))
    (_, t0, t1), = [s for s in full.spans if s[0] == "bench.window"]
    return dst, tr.clip(full, t0, t1)


def _record(trace):
    from bench import derive
    return derive.Run(cfg={}, loop="open", seconds=0.0, t_open=0.0, close=0.0,
                      setup_s=0.0, reqs=[], steps=[], peaks={}, compile_s=0.0,
                      trace=trace)


@pytest.mark.parametrize("metric", ["queue_wait_p95_ms", "admit_idle_ms",
                                    "decode_idle_ms.tails", "decode_idle_ms.tput"])
def test_a_program_without_engine_spans_reads_nothing(tmp_path, monkeypatch, metric):
    """The trace recorded before the engine had spans: each reader finds
    nothing to read, and does not raise."""
    _, clipped = _laid_out(tmp_path, "v5e_code_completion.xplane.pb.gz",
                           "coder33b.code_completion")
    monkeypatch.setattr(run, "CACHE", tmp_path)
    assert engine_spans.of(_record(clipped)) is None
    assert run.reader(metric)(_record(clipped)) is None
    assert run.reader(metric)(_record(None)) is None


# ------------------------------------------------------------ traced tiny run
BENCH = {
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s"},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "queue_wait_p95_ms", "unit": "ms", "moves": "ttft_p95_s"},
        {"name": "admit_idle_ms", "unit": "ms", "moves": "ttft_p95_s"},
        {"name": "decode_idle_ms.tails", "unit": "ms", "moves": "itl_p95_ms"}]}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced open-loop run at test size; its result line and the engine
    spans of its trace."""
    from repro.serving import engine as engine_mod
    from repro.serving.kv_cache import SlotCache
    params, admit = engine_mod.image_params, SlotCache.admit
    cache = run.CACHE
    run.CACHE = tmp_path_factory.mktemp("bench_cache")
    try:
        cfg = json.loads((HERE / "testdata" / "tiny.json").read_text())
        res = run.run_cell(
            {"name": "tiny.spans", "chips": 1}, cfg,
            traffic.load("tiny_open", HERE / "testdata"), seed=11, seconds=1.5, trace=True,
            bench=BENCH, limits={"max_logit_gap": 0.04, "wrong_length": 0},
            peaks={"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            device=jax.devices()[0], clock=run.CompileClock())["result"]
        path = tr.find(str(run.CACHE / "trace" / "tiny.spans"))
        yield res, engine_spans.load(path)[1], path
    finally:
        run.CACHE = cache
        engine_mod.image_params, SlotCache.admit = params, admit
        engine_mod._IMAGE_CACHE.clear()


def test_traced_run_records_every_engine_span(traced):
    res, spans, _ = traced
    assert res["correct"]
    assert {n for n, *_ in spans} == ALL_SPANS
    for n, _, _, attrs in spans:
        assert ("rid" in attrs) == (n in REQUEST_SPANS), (n, attrs)


def test_admission_spans_nest_under_their_request(traced):
    _, spans, _ = traced
    admits = {a["rid"]: (s, e) for n, s, e, a in spans if n == "engine.admit"}
    assert admits
    for n, s, e, a in spans:
        if n in ADMIT_CHILDREN:
            s0, e0 = admits[a["rid"]]
            assert s0 <= s <= e <= e0, (n, a["rid"])
    sample = [(s, e) for n, s, e, _ in spans if n == "engine.sample"]
    decode = [(s, e) for n, s, e, _ in spans if n == "engine.decode"]
    assert len(sample) == len(decode)
    assert all(d0 <= s0 <= s1 <= d1 for (s0, s1), (d0, d1) in zip(sample, decode))


def test_each_request_is_enqueued_before_it_is_admitted(traced):
    _, spans, _ = traced
    enq = {a["rid"]: e for n, _, e, a in spans if n == "engine.enqueue"}
    admits = [(a["rid"], s) for n, s, _, a in spans if n == "engine.admit"]
    assert admits and all(enq[rid] <= s for rid, s in admits)


def test_traced_run_reads_the_engine_span_metrics(traced):
    res, _, _ = traced
    got = res["metrics"]
    assert set(got) == {"queue_wait_p95_ms", "admit_idle_ms", "decode_idle_ms.tails"}
    assert got["queue_wait_p95_ms"]["value"] >= 0.0
    assert got["admit_idle_ms"]["value"] > 0.0 and got["decode_idle_ms.tails"]["value"] > 0.0


def test_span_report_accounts_for_the_window(traced):
    from bench import span_report
    res, spans, path = traced
    rep = span_report.report(path)
    assert rep["window_s"] == pytest.approx(res["device"]["window_s"])
    assert rep["busy_s"] + sum(rep["idle_by_engine_span"].values()) == \
        pytest.approx(rep["window_s"])
    assert rep["idle_by_harness_span"] == pytest.approx(
        dict(res["breakdown"]["idle_gaps"]))
    assert rep["spans_started_in_window"]["engine.admit"] == \
        sum(1 for sp in spans if sp[0] == "engine.admit")


# ------------------------------------------------- recorded trace from the chip
# Host spans and device programs are on one clock only after the offset that
# ``trace.load`` takes from ``DoEnqueueProgram``. A program may start no earlier
# and end no later than this much outside the host span that launched and
# waited for it; on the recording every such program lies inside by 0.59 ms
# or more, and a clock off by a millisecond would break this.
ALIGN_TOL_S = 1e-4


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """0.6 s of ``coder33b.code_completion_buckets`` traced on one TPU v5e
    (seed 2147494101: four requests, two of which arrive together)."""
    root = tmp_path_factory.mktemp("bench_cache")
    path, clipped = _laid_out(root, "v5e_engine_spans.xplane.pb.gz",
                              "coder33b.code_completion_buckets")
    return root, tr.load(str(path)), clipped, engine_spans.load(str(path))[1]


@pytest.mark.parametrize("program,span", [("jit_decode_step", "engine.decode"),
                                          ("jit_prefill", "engine.prefill")])
def test_recorded_programs_lie_inside_their_engine_span(recorded, program, span):
    _, full, _, spans = recorded
    progs = [(s, e) for n, s, e in full.programs if n.startswith(program)]
    around = [(s, e) for n, s, e, _ in spans if n == span]
    assert progs and len(progs) == len(around)
    for s, e in progs:
        assert any(s0 - ALIGN_TOL_S <= s and e <= e0 + ALIGN_TOL_S for s0, e0 in around), \
            (program, s, e)


def test_recorded_engine_spans(recorded):
    _, _, clipped, spans = recorded
    counts = {n: sum(1 for sp in spans if sp[0] == n) for n in ALL_SPANS}
    assert counts == {"engine.route": 4, "engine.enqueue": 4, "engine.admit": 4,
                      "engine.prefill": 4, "engine.insert": 4, "engine.first_token": 4,
                      "engine.step": 8, "engine.decode": 8, "engine.sample": 8,
                      "engine.complete": 0}
    t0, t1 = engine_spans.window(clipped)
    assert engine_spans.queue_waits(spans, t0, t1) == pytest.approx(
        [3.656e-05, 0.102421582, 4.0431e-05, 0.027115547])
    # the harness's breakdown puts the same idle under bench.admit as the
    # engine puts under engine.insert, the call both wrap
    by_engine = engine_spans.idle_by_innermost(clipped, spans, t0, t1)
    assert by_engine["engine.insert"] == pytest.approx(
        tr.idle_by_span(clipped, t0, t1)["bench.admit"])
    assert tr.busy(clipped) + sum(by_engine.values()) == pytest.approx(t1 - t0)


@pytest.mark.parametrize("metric,value", [("queue_wait_p95_ms", 91.12567675),
                                          ("admit_idle_ms", 13.822901),
                                          ("decode_idle_ms.tails", 2.99415337),
                                          ("decode_idle_ms.tput", 2.99415337)])
def test_recorded_trace_reads(recorded, monkeypatch, metric, value):
    root, _, clipped, _ = recorded
    monkeypatch.setattr(run, "CACHE", root)
    assert run.reader(metric)(_record(clipped)) == pytest.approx(value)


def test_recorded_span_report(recorded):
    """Every idle second under ``bench.step`` and ``bench.admit`` lies in an
    engine span other than ``engine.step`` alone."""
    from bench import span_report
    root, *_ = recorded
    rep = span_report.report(tr.find(str(root / "trace" / "coder33b.code_completion_buckets")))
    under = rep["idle_under_step_and_admit"]
    assert under["total"] == pytest.approx(0.076544369)
    assert under["by_engine_span"] == pytest.approx(
        {"engine.prefill": 0.003534612, "engine.insert": 0.04739118,
         "engine.first_token": 2.2816e-05, "engine.decode": 0.024738563,
         "engine.sample": 0.000857198})
    assert rep["covered"] == rep["covered_by_a_child"] == pytest.approx(1.0)
    assert rep["spans_started_in_window"] == {"engine.admit": 4, "engine.decode": 8}
