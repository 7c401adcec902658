"""The reduction from a trace to busy time, idle gaps and per-span device time."""
import pytest

from bench import trace as tr


@pytest.fixture
def small():
    return tr.Trace(
        ops=[(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)],
        programs=[("jit_prefill", 0.0, 2.0), ("jit_dynamic_update_slice", 2.2, 2.5),
                  ("jit_decode_step", 3.0, 4.0)],
        spans=[("bench.window", 0.0, 5.0), ("bench.step", 0.0, 2.6),
               ("bench.admit", 2.1, 2.6), ("bench.observe", 2.6, 3.5)])


def test_busy_is_the_union_of_ops(small):
    assert tr.union(small.ops) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.busy(small) == pytest.approx(3.0)


def test_gaps(small):
    assert tr.gaps(small, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert tr.program_gaps(small) == pytest.approx([0.2, 0.5])


def test_program_time(small):
    assert tr.program_time(small, "jit_prefill") == (2.0, 1)
    assert tr.program_time(small, "jit_decode_step") == (1.0, 1)
    assert tr.top_programs(small)[0] == ("jit_prefill", 2.0)


def test_device_time_under_a_span(small):
    secs, n = tr.time_under(small, "bench.admit")
    assert secs == pytest.approx(0.3) and n == 1


def test_idle_by_innermost_span(small):
    idle = tr.idle_by_span(small, 0.0, 5.0)
    assert idle == pytest.approx({"bench.admit": 1.0, "bench.window": 1.0})


def test_clip(small):
    c = tr.clip(small, 1.0, 4.5)
    assert c.ops == [(1.0, 2.0), (3.0, 4.0)]
    assert [p[0] for p in c.programs] == ["jit_prefill", "jit_dynamic_update_slice",
                                          "jit_decode_step"]
    assert c.programs[0][1] == 1.0


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """0.6 s of ``coder33b.code_completion`` traced on one TPU v5e."""
    import gzip
    import shutil
    from pathlib import Path
    src = Path(__file__).resolve().parent / "testdata" / "v5e_code_completion.xplane.pb.gz"
    dst = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    with gzip.open(src) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    full = tr.load(str(dst))
    (_, t0, t1), = [s for s in full.spans if s[0] == "bench.window"]
    return tr.clip(full, t0, t1), t0, t1


def test_recorded_trace_reduces_as_recorded(recorded):
    c, t0, t1 = recorded
    assert t1 - t0 == pytest.approx(0.586148665)
    assert len(c.programs) == 96
    assert tr.busy(c) == pytest.approx(0.515004149)
    assert tr.program_time(c, "jit_prefill") == pytest.approx((0.167042569, 2))
    assert tr.program_time(c, "jit_decode_step") == pytest.approx((0.33830287, 14))
    assert tr.time_under(c, "bench.admit") == pytest.approx((0.009657752, 2))


def test_recorded_trace_busy_and_idle_fill_the_window(recorded):
    c, t0, t1 = recorded
    idle = tr.idle_by_span(c, t0, t1)
    assert tr.busy(c) + sum(idle.values()) == pytest.approx(t1 - t0)
    assert idle["bench.admit"] == pytest.approx(0.02374648)
