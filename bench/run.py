"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``. With ``--trace 0`` the line carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window. Either way the run then checks what it served against
the plain reference and prints each number compared beside its limit.

It needs a TPU: where JAX finds none, or fewer chips than the cell asks
for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"


class CompileClock:
    """Sums XLA backend compile seconds, process-wide (``jax.monitoring``)."""

    def __init__(self):
        import jax
        self.secs, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, json.loads((ROOT / conf["file"]).read_text())


def metrics_of(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = bench["end_to_end"]
    mine = {m["name"] for m in e2e if cell in m.get("workloads", [cell])}
    if not trace:
        return [m for m in e2e if m["name"] in mine]
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in mine]


def reader(name: str):
    """The reader of a metric: ``metrics/<name>.py``, or for a quantity split
    by the end-to-end metric it moves (``mfu.tput``), ``metrics/mfu.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs[0]


def instrument(h):
    """Host spans around the calls into the engine's layers, on the
    profiler's clock: submit, worker step, cache insert, prefill, decode.
    Returns what undoes the class-level wrap."""
    import jax
    from repro.serving.kv_cache import SlotCache

    def spanned(name, fn):
        def call(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return call

    h.span = jax.profiler.TraceAnnotation
    admit = SlotCache.admit
    SlotCache.admit = spanned("bench.admit", admit)
    for inst in h.instances():
        inst._prefill = spanned("bench.prefill", inst._prefill)
        inst._decode = spanned("bench.decode", inst._decode)

    def undo():
        SlotCache.admit = admit
    return undo


def run_cell(cell: dict, cfg: dict, mix, *, seed: int, seconds: float, trace: bool,
             bench: dict, limits: dict, peaks: dict, device, clock,
             t_start: float = T_START) -> dict:
    """One run of a cell on ``device``: set-up, window, metrics, check."""
    import jax

    from bench import check, derive, harness
    from bench import trace as tr

    h = harness.Harness(cfg, mix, seed)
    h.warm_up()
    compile_setup = clock.secs
    compiles_setup = clock.compiles
    log_dir = CACHE / "trace" / cell["name"]
    on_open, undo = None, None
    if trace:
        undo = instrument(h)
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        on_open = lambda: jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    t_open = time.perf_counter()
    close = h.run(t_open, seconds, on_open=on_open)
    window_steps = list(h.steps)
    if trace:
        jax.profiler.stop_trace()
    compiles_window = clock.compiles - compiles_setup
    h.drain()
    end = time.perf_counter()
    if undo:
        undo()
    stats = device.memory_stats() or {}
    run = derive.Run(cfg=cfg, loop=mix.loop, seconds=seconds, t_open=t_open,
                     close=close, setup_s=t_open - t_start, reqs=list(h.reqs),
                     steps=window_steps, peaks=peaks, compile_s=compile_setup,
                     end=end)
    breakdown = None
    t_trace = time.perf_counter()
    if trace:
        full = tr.load(tr.find(str(log_dir)))
        win = [s for s in full.spans if s[0] == "bench.window"]
        t0, t1 = win[0][1], win[0][2]
        run.trace, run.window_s = tr.clip(full, t0, t1), t1 - t0
        idle = tr.idle_by_span(run.trace, t0, t1)
        breakdown = {
            "device_ops": [[n, s] for n, s in tr.top_programs(run.trace)],
            "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                                key=lambda x: -x[1])[:10]}
    values = {}
    for m in metrics_of(bench, cell["name"], trace):
        v = reader(m["name"])(run)
        if v is None:
            print(f"bench: {m['name']}: nothing to read in this run", file=sys.stderr)
        else:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    finished = [r for r in h.reqs if r.served is not None]
    attempted = len(h.reqs)
    failed = sum(1 for r in h.reqs if mix.loop == "open" and r.served is None)
    h.free()
    del h
    gc.collect()
    t_check = time.perf_counter()
    nums = check.numbers(cfg, seed, finished, mix.output_tokens)
    correct = check.verdict(nums, limits)
    t_done = time.perf_counter()
    lines = [f"seconds: set-up {t_open - t_start}, window {close - t_open}, "
             f"drain {end - close}, trace reading {t_check - t_trace}, "
             f"check {t_done - t_check}"]
    lines += [f"check {k}: {nums[k]!r} (limit {v!r})" for k, v in limits.items()]
    lines.append(f"check tokens_compared: {nums['tokens_compared']}; "
                 f"compiles in the window: {compiles_window}; correct: {correct}")
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": values,
              "device": {"platform": device.platform, "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if trace:
        result["device"]["busy_s"] = tr.busy(run.trace)
        result["device"]["window_s"] = run.window_s
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": nums[k], "limit": v} for k, v in limits.items()}
    return {"result": result, "lines": lines}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec()
    cell, cfg = cell_of(bench, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    device = require_chips(cell["chips"])
    peaks = peaks_of(device.device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    from bench import check, traffic
    enable_compile_cache()
    out = run_cell(cell, cfg, traffic.load(cell["traffic"]), seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), bench=bench,
                   limits=check.limits(cell["name"]), peaks=peaks,
                   device=device, clock=CompileClock())
    sys.stdout.flush()
    for line in out["lines"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
