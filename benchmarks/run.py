"""Benchmark harness — one benchmark per paper claim/figure + system perf.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_tree_scaling       paper Fig.1 claim: replicate-and-front scales
  bench_lb_policies        stateless vs stateful LBs (paper §II)
  bench_concurrency        RQ-A (paper §III.A) — per-policy instance counts
  bench_emulation          RQ-B (paper §III.B) — fidelity + emulation speedup
  bench_serving_engine     real-model worker throughput (Fig.2 step 1 rig)
  bench_kernels            Pallas kernels in the interpreter vs oracle (CPU timings)
  bench_workload_scenarios named traffic shapes + >=1M-request bursty probe
  bench_autoscaler_scenarios autoscaler policy menu vs static replicate
  bench_fault_scenarios    chaos layer: zone outage A/B + retry storm
  bench_gateway            front-door gateway: noisy-neighbor flood A/B
  bench_workflows          DAG workflows: stage-blind vs DAG-aware routing
  bench_sim_throughput     simulator events/s (testbed capacity)
  roofline_table           dry-run artifacts summary (if sweep has run)
"""
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

# every _row also lands here; main() dumps them to benchmarks/out/ as the
# JSON artifact CI uploads (gitignored locally)
ROWS = []
OUT_DIR = os.path.join(os.path.dirname(__file__), "out")


def _row(name, us, derived="", **metrics):
    """Emit one benchmark row. ``derived`` stays the human-facing
    ``k=v;k=v`` string; ``metrics`` kwargs land as structured numeric
    fields under ``row["metrics"]`` in the JSON artifact so CI gates
    read typed values instead of regex-parsing the display string."""
    print(f"{name},{us:.2f},{derived}")
    row = {"name": name, "us_per_call": round(us, 2), "derived": derived}
    if metrics:
        row["metrics"] = metrics
    ROWS.append(row)


def bench_tree_scaling():
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree, replicate
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      poisson_load, summarize)
    from repro.core.types import FunctionConfig
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=4,
                             cold_start_s=0.2))
    base = build_tree(8, fanout=4)
    for times in (1, 2, 4, 8):
        tree = base if times == 1 else replicate(base, times=times)
        sim = Simulator(tree, store, SyntheticServiceModel(seed=2), seed=7)
        rps = 300 * times
        poisson_load(sim, fn="fn", rps=rps, duration_s=10, seed=3)
        t0 = time.perf_counter()
        s = summarize(sim.run())
        wall = time.perf_counter() - t0
        _row(f"tree_scaling_x{times}", 1e6 * s["p99"],
             f"workers={8*times};rps={rps};p50_ms={s['p50']*1e3:.1f};"
             f"fail={s['fail_rate']:.3f};sim_wall_s={wall:.1f}")


def bench_lb_policies():
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      poisson_load, summarize)
    from repro.core.types import FunctionConfig
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=4,
                             cold_start_s=0.2))
    for pol in ("random", "round_robin", "least_loaded", "pow2",
                "warm_affinity"):
        sim = Simulator(build_tree(16, fanout=4, leaf_policy=pol), store,
                        SyntheticServiceModel(seed=2), seed=7)
        poisson_load(sim, fn="fn", rps=500, duration_s=10, seed=3)
        s = summarize(sim.run())
        _row(f"lb_policy_{pol}", 1e6 * s["p99"],
             f"p50_ms={s['p50']*1e3:.2f};cold={s['cold_rate']:.3f}")


def bench_concurrency():
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      poisson_load, summarize)
    from repro.core.types import FunctionConfig
    for name, c in (("lambda_c1", 1), ("knative_c8", 8), ("azure_unlim", 0)):
        store = ConfigStore()
        store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=c,
                                 cold_start_s=0.25, idle_timeout_s=8.0,
                                 max_instances_per_worker=16))
        sim = Simulator(build_tree(16, fanout=4), store,
                        SyntheticServiceModel(seed=2), seed=7)
        poisson_load(sim, fn="fn", rps=400, duration_s=20, seed=11)
        s = summarize(sim.run())
        inst = sum(w.instances_started for w in sim.workers.values())
        _row(f"concurrency_{name}", 1e6 * s["p99"],
             f"instances={inst};p50_ms={s['p50']*1e3:.1f};"
             f"cold={s['cold_rate']:.3f}")


def bench_emulation():
    from repro.core.config_store import ConfigStore
    from repro.core.emulation import (EmulatedServiceModel, RidgeWorkerModel,
                                      fidelity_report, telemetry_matrix)
    from repro.core.router import build_tree
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      poisson_load)
    from repro.core.types import FunctionConfig
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=4,
                             cold_start_s=0.2))
    real = Simulator(build_tree(8, fanout=4), store,
                     SyntheticServiceModel(seed=2), seed=5)
    poisson_load(real, fn="fn", rps=150, duration_s=15, seed=4)
    t0 = time.perf_counter()
    real_res = real.run()
    t_real = time.perf_counter() - t0
    X, y, ok = telemetry_matrix([r for r in real.telemetry if r.latency > 0])
    t0 = time.perf_counter()
    model = RidgeWorkerModel.fit(X, y, ok)
    t_fit = time.perf_counter() - t0
    emu = Simulator(build_tree(8, fanout=4), store,
                    EmulatedServiceModel(model, seed=0), seed=5)
    poisson_load(emu, fn="fn", rps=150, duration_s=15, seed=4)
    t0 = time.perf_counter()
    emu_res = emu.run()
    t_emu = time.perf_counter() - t0
    rep = fidelity_report(np.array([r.latency for r in real_res if r.ok]),
                          np.array([r.latency for r in emu_res if r.ok]))
    _row("emulation_fidelity", 1e6 * t_fit,
         f"p50_err={rep['p50_rel_err']:.3f};p95_err={rep['p95_rel_err']:.3f};"
         f"p99_err={rep['p99_rel_err']:.3f};ks={rep['ks']:.3f}")
    _row("emulation_speed", 1e6 * t_emu / max(len(emu_res), 1),
         f"vs_groundtruth_us={1e6*t_real/max(len(real_res),1):.1f}")


def bench_serving_engine():
    from repro.core.config_store import ConfigStore, ImageRegistry
    from repro.core.router import build_tree
    from repro.core.types import FunctionConfig, Request
    from repro.serving.engine import Engine
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=4,
                             gen_tokens=4))
    eng = Engine(build_tree(1, fanout=2), store, ImageRegistry(), max_len=64)
    # warm (exclude compile)
    eng.submit(Request(fn="fn", arrival_t=0.0, size=8))
    eng.run()
    t0 = time.perf_counter()
    n = 8
    for i in range(n):
        eng.submit(Request(fn="fn", arrival_t=0.0, size=8))
    res = eng.run()
    wall = time.perf_counter() - t0
    toks = sum(4 for _ in res)
    _row("serving_engine_warm", 1e6 * wall / n,
         f"tok_per_s={toks/wall:.1f};batched_slots=4")
    w = list(eng.workers.values())[0]
    inst = w.instances["fn"][0]
    _row("serving_cold_start", 1e6 * inst.cold_start_s, "compile+init")


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref as R
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba_scan import mamba_scan
    rng = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    for name, fn in (
            ("flash_attn_interpret",
             lambda: flash_attention(q, k, v, causal=True, block_q=128,
                                     block_k=128, interpret=True)),
            ("flash_attn_ref_xla",
             lambda: R.flash_attention_ref(q, k, v, causal=True))):
        fn()
        t0 = time.perf_counter()
        fn()
        _row(name, 1e6 * (time.perf_counter() - t0),
             f"S={S};flops={4*H*hd*S*S*B//2}")
    dt = jax.nn.softplus(jax.random.normal(ks[0], (1, 256, 128))) * 0.1
    x = jax.random.normal(ks[1], (1, 256, 128))
    Bc = jax.random.normal(ks[2], (1, 256, 16))
    A = -jnp.exp(jax.random.normal(ks[0], (128, 16)) * 0.2)
    D = jnp.ones(128)
    t0 = time.perf_counter()
    mamba_scan(dt, x, Bc, Bc, A, D, chunk=64, block_d=64, interpret=True)
    _row("mamba_scan_interpret", 1e6 * (time.perf_counter() - t0),
         "S=256;DI=128;N=16")


def bench_workload_scenarios():
    """Named workload shapes (repro.workloads) end-to-end, then a ≥1M-
    request bursty multi-function capacity probe reporting events/s.
    REPRO_EVENT_BACKEND selects the event-queue backend (default
    single_heap) — CI runs this bench once per backend and fails if
    `sharded` regresses events/s on the capacity probe."""
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      summarize)
    from repro.core.types import FunctionConfig
    from repro.workloads import (BurstyArrivals, FunctionProfile,
                                 MixedWorkload, SizeDist, build_scenario,
                                 install_demo_configs)
    backend = os.environ.get("REPRO_EVENT_BACKEND", "single_heap")
    for name in ("steady", "flash_crowd", "daily_cycle", "multi_tenant"):
        wl = build_scenario(name, duration_s=10.0, seed=3)
        store = ConfigStore()
        install_demo_configs(store, wl)
        sim = Simulator(build_tree(16, fanout=4), store,
                        SyntheticServiceModel(seed=2), seed=7,
                        event_backend=backend)
        n = sim.load(wl)
        t0 = time.perf_counter()
        s = summarize(sim.run())
        wall = time.perf_counter() - t0
        eps = sim.events_processed / max(wall, 1e-9)
        _row(f"scenario_{name}", 1e6 * s["p99"],
             f"n={n};p50_ms={s['p50']*1e3:.1f};cold={s['cold_rate']:.3f};"
             f"fail={s['fail_rate']:.3f};events_per_s={eps:.0f}",
             n=n, events_per_s=eps, fail_rate=s["fail_rate"])
    # capacity probe: MMPP bursts over a three-tenant mix, ≥1M requests
    store = ConfigStore()
    for fn in ("chat", "embed", "batch"):
        store.put(FunctionConfig(name=fn, arch="tiny_lm", concurrency=8,
                                 cold_start_s=0.1, idle_timeout_s=30.0,
                                 max_instances_per_worker=16))
    profiles = [
        FunctionProfile("chat", weight=6.0, size=SizeDist.lognormal(24, 0.5)),
        FunctionProfile("embed", weight=3.0, size=SizeDist.uniform(8, 48)),
        FunctionProfile("batch", weight=1.0, size=SizeDist.const(96)),
    ]
    wl = MixedWorkload(
        BurstyArrivals(rate_on=40000.0, rate_off=10000.0,
                       mean_on_s=1.0, mean_off_s=3.0),
        profiles, duration_s=64.0, seed=3)
    sim = Simulator(build_tree(512, fanout=16), store,
                    SyntheticServiceModel(seed=2), seed=7,
                    event_backend=backend)
    t0 = time.perf_counter()
    n = sim.load(wl)
    t_gen = time.perf_counter() - t0
    assert n >= 1_000_000, f"capacity probe must drive >=1M requests, got {n}"
    t0 = time.perf_counter()
    s = summarize(sim.run())
    wall = time.perf_counter() - t0
    _row("scenario_bursty_1m", 1e6 * wall / n,
         f"requests={n};events={sim.events_processed};"
         f"events_per_s={sim.events_processed/wall:.0f};"
         f"req_per_s={n/wall:.0f};gen_s={t_gen:.1f};"
         f"p99_ms={s['p99']*1e3:.1f};fail={s['fail_rate']:.4f}",
         requests=n, events=sim.events_processed,
         events_per_s=sim.events_processed / wall, req_per_s=n / wall)


def bench_workload_generation():
    """ISSUE-8 micro-probe: scalar (`requests()`) vs vectorized
    (`generate_bulk`) generation requests/s, per arrival kind, at the
    ~1M-request scale. CI gates bulk >= 10x scalar on the poisson row
    (WORKLOAD_GEN_PROBE_S scales the horizon for local runs)."""
    from repro.workloads import (BurstyArrivals, DiurnalArrivals,
                                 FunctionProfile, MixedWorkload,
                                 PoissonArrivals, SizeDist, TraceArrivals)
    dur = float(os.environ.get("WORKLOAD_GEN_PROBE_S", "50"))
    rate = 20000.0                         # 20k rps x 50 s = 1M requests
    profiles = [
        FunctionProfile("interactive", weight=3.0,
                        size=SizeDist.lognormal(24, 0.6), slo_p95_s=0.5),
        FunctionProfile("batch", weight=1.0,
                        size=SizeDist.uniform(64, 512)),
        FunctionProfile("ping", weight=1.0, size=SizeDist.const(4)),
    ]
    kinds = {
        "poisson": PoissonArrivals(rate),
        "bursty": BurstyArrivals(rate_on=3.0 * rate, rate_off=rate / 3.0,
                                 mean_on_s=0.5, mean_off_s=1.0),
        "diurnal": DiurnalArrivals(base_rate=rate, amplitude=0.8,
                                   period_s=dur),
        "trace": TraceArrivals([1.0 / rate] * 997, loop=True),
    }
    speedups = {}
    for name in sorted(kinds):
        wl = MixedWorkload(kinds[name], profiles, duration_s=dur, seed=3)
        t0 = time.perf_counter()
        n_scalar = sum(1 for _ in wl.requests())
        t_scalar = time.perf_counter() - t0
        t0 = time.perf_counter()
        batch = wl.generate_bulk()
        t_bulk = time.perf_counter() - t0
        scalar_rps = n_scalar / t_scalar
        bulk_rps = len(batch) / t_bulk
        speedups[name] = bulk_rps / scalar_rps
        _row(f"workload_gen_{name}", 1e6 * t_bulk / max(1, len(batch)),
             f"n_scalar={n_scalar};n_bulk={len(batch)};"
             f"scalar_req_per_s={scalar_rps:.0f};"
             f"bulk_req_per_s={bulk_rps:.0f};"
             f"speedup={bulk_rps / scalar_rps:.1f}x",
             n_bulk=len(batch), scalar_req_per_s=scalar_rps,
             bulk_req_per_s=bulk_rps, speedup=bulk_rps / scalar_rps)
    _row("workload_gen_speedup_min", 0.0,
         f"min_over_kinds={min(speedups.values()):.1f}x",
         min_over_kinds=min(speedups.values()))


def bench_autoscaler_scenarios():
    """Autoscaler policy menu vs the paper's static replicate recipe under
    `flash_crowd` and `daily_cycle` (repro.autoscale). Reports p95,
    fail/cold rates, and worker-seconds (the replica-seconds cost proxy:
    branches are uniform, so the two are proportional). ``slo_aware``
    scales against the scenario's per-function `slo_p95_s` targets and
    additionally reports per-function p95 vs SLO."""
    from repro.autoscale import Autoscaler, build_pool, get_autoscaler
    from repro.core.config_store import ConfigStore
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      summarize)
    from repro.workloads import build_scenario, install_demo_configs
    shapes = {
        "flash_crowd": dict(duration_s=30.0, seed=3, base_rps=12.0,
                            burst_rps=1000.0, mean_burst_s=2.0,
                            mean_calm_s=10.0),
        "daily_cycle": dict(duration_s=60.0, seed=3, mean_rps=150.0,
                            period_s=60.0),
    }
    for shape, overrides in shapes.items():
        for policy in ("static", "reactive", "target_concurrency",
                       "predictive", "slo_aware"):
            wl = build_scenario(shape, **overrides)
            store = ConfigStore()
            install_demo_configs(store, wl)
            # static = provisioned once at 3 branches (replicate recipe);
            # scalers start at 1 branch and may grow to 8
            branches = 3 if policy == "static" else 1
            sim = Simulator(build_pool(branches, 2), store,
                            SyntheticServiceModel(seed=2), seed=7,
                            worker_capacity_slots=1)
            pol = (get_autoscaler("slo_aware", slo_p95_s=wl.slo_targets())
                   if policy == "slo_aware" else policy)
            scaler = Autoscaler(pol, interval_s=0.25, window_s=2.0,
                                min_replicas=1, max_replicas=8,
                                workers_per_replica=2, cooldown_s=2.0)
            sim.attach_autoscaler(scaler)
            n = sim.load(wl)
            t0 = time.perf_counter()
            results = sim.run()
            s = summarize(results)
            wall = time.perf_counter() - t0
            sm = scaler.summary()
            extra = ""
            if policy == "slo_aware":
                parts = []
                for fn, slo in sorted(wl.slo_targets().items()):
                    lat = np.array([r.latency for r in results
                                    if r.ok and r.fn == fn])
                    p95 = float(np.percentile(lat, 95)) if len(lat) else 0.0
                    parts.append(f"{fn}={p95*1e3:.0f}/{slo*1e3:.0f}ms")
                extra = ";fn_p95_vs_slo=" + ",".join(parts)
            _row(f"autoscale_{shape}_{policy}", 1e6 * s["p95"],
                 f"n={n};p95_ms={s['p95']*1e3:.1f};"
                 f"fail={s['fail_rate']:.4f};cold={s['cold_rate']:.3f};"
                 f"worker_s={sm['worker_seconds']:.0f};"
                 f"max_replicas={sm['max_replicas_seen']};"
                 f"ups={sm['scale_ups']};downs={sm['scale_downs']};"
                 f"sim_wall_s={wall:.1f}{extra}")


def bench_placement():
    """Placement x routing matrix on the memory-skewed `multi_tenant`
    scenario (heterogeneous per-tenant replica footprints, memory-capped
    workers, slo_aware autoscaling). Reports per-function p95 vs SLO,
    worker-seconds, and cold rate — the ISSUE-4 acceptance surface:
    best_fit_memory + deadline_aware should meet every SLO at lower cost
    than the first_fit + least_loaded baseline. The matrix cells live in
    examples/placement_study.py (one definition for CI and the study)."""
    from repro.core.simulator import summarize
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "examples"))
    from placement_study import CELLS, run_cell
    for placer, leaf, inner in CELLS:
        t0 = time.perf_counter()
        sim, scaler, results, per_fn = run_cell(placer, leaf, inner)
        wall = time.perf_counter() - t0
        s = summarize(results)
        sm = scaler.summary()
        parts = [f"{fn}={p95*1e3:.0f}/{slo*1e3:.0f}ms"
                 for fn, (p95, slo) in per_fn.items()]
        _row(f"placement_{placer}_{leaf}", 1e6 * s["p95"],
             f"n={len(results)};fail={s['fail_rate']:.4f};"
             f"cold={s['cold_rate']:.3f};"
             f"worker_s={sm['worker_seconds']:.0f};"
             f"fn_p95_vs_slo={','.join(parts)};sim_wall_s={wall:.1f}")


def bench_fault_scenarios():
    """Chaos-layer A/B (repro.core.faults): the seeded `zone_outage`
    scenario across {spread, spread_zones} x {no retry, retry budget 2},
    plus a `retry_storm` probe of the storm guard. The acceptance shape
    (tests/test_faults.py): failure-domain-aware placement + a retry
    budget rides through the outage; the zone-blind no-retry cell loses
    its warm capacity and its in-flight work in one event."""
    from repro.autoscale import build_pool
    from repro.core.config_store import ConfigStore
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      summarize)
    from repro.core.types import FunctionConfig
    from repro.workloads import build_scenario

    def _sim(wl, *, zones, branches, wpb, placer, retry_budget, prewarm,
             **sim_kw):
        # memory-capped one-replica workers: a pre-warmed steady state
        # where *placement* decided which zones hold each function's
        # warm capacity, and the surviving zone has no memory headroom
        # to rebuild the dead zone's share. The zone-blind cell pays the
        # outage in dead in-flight work plus a function stranded with no
        # warm capacity anywhere; spread_zones keeps half of every
        # function's replicas in the surviving zone and rides through.
        store = ConfigStore()
        for p in wl.profiles:
            store.put(FunctionConfig(name=p.fn, arch="tiny_lm",
                                     concurrency=4, cold_start_s=1.0,
                                     timeout_s=8.0))
        sim = Simulator(build_pool(branches, wpb,
                                   leaf_policy="warm_least_loaded",
                                   inner_policy="deadline_aware"),
                        store, SyntheticServiceModel(seed=2, fail_rate=0.0),
                        seed=7, zones=zones, placer=placer,
                        worker_memory_mb=600, cold_start_default_s=1.0,
                        retry_budget=retry_budget, **sim_kw)
        for p in wl.profiles:
            for _ in range(prewarm):
                sim.place_prewarm(p.fn)
        sim.load(wl)
        return sim

    for placer in ("spread", "spread_zones"):
        for retry_budget in (0, 2):
            wl = build_scenario("zone_outage", seed=3)
            sim = _sim(wl, zones=2, branches=2, wpb=4, placer=placer,
                       retry_budget=retry_budget, prewarm=4)
            t0 = time.perf_counter()
            results = sim.run()
            s = summarize(results)
            wall = time.perf_counter() - t0
            parts = []
            for fn, slo in sorted(wl.slo_targets().items()):
                rows = [r for r in results if r.fn == fn]
                att = (sum(1 for r in rows if r.ok and r.latency <= slo)
                       / max(1, len(rows)))
                parts.append(f"{fn}={att:.3f}")
            fstats = sim.faults.summary()
            _row(f"fault_zone_outage_{placer}_retry{retry_budget}",
                 1e6 * s["p95"],
                 f"n={s['n']};fail={s['fail_rate']:.4f};"
                 f"slo_attainment={','.join(parts)};"
                 f"retries={sim.retries_scheduled};"
                 f"zone_outages={fstats['zone_outages']};"
                 f"sim_wall_s={wall:.1f}")

    # retry storm: 2 of 3 zones fail at once under heavy load; the storm
    # guard caps concurrent retries and sheds the rest of the blast wave
    # instead of re-offering all of it to the lone surviving zone
    wl = build_scenario("retry_storm", seed=3, rps=1500.0)
    sim = _sim(wl, zones=3, branches=3, wpb=2, placer="spread_zones",
               retry_budget=3, retry_storm_cap=32, prewarm=3)
    t0 = time.perf_counter()
    s = summarize(sim.run())
    wall = time.perf_counter() - t0
    _row("fault_retry_storm", 1e6 * s["p95"],
         f"n={s['n']};fail={s['fail_rate']:.4f};"
         f"retries={sim.retries_scheduled};shed={sim.retries_shed};"
         f"cap=32;sim_wall_s={wall:.1f}")


def bench_gateway():
    """ISSUE-9 acceptance probe: the `noisy_neighbor` A/B (gateway on
    vs off, same fleet — equal worker-seconds). Two rigs, the same ones
    tests/test_gateway.py enforces:

    `noisy` — the flood is capped at one replica per worker, so the
    baseline queues it to the 8 s timeout horizon and ~14k hedge clones
    double its service demand; the gateway's batch admission ceiling
    keeps its outstanding work at 6, nothing hedges, and the same fleet
    clears ~1.65x the goodput.

    `pinned` — a roomier fleet with no replica cap: the flood wins
    every memory slot at t=0 and pins `embed` at *zero* completions;
    the admission ceiling bounds the flood's footprint and both
    interactive tenants come back within SLO."""
    from repro.autoscale import build_pool
    from repro.core.config_store import ConfigStore
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      summarize)
    from repro.core.types import FunctionConfig
    from repro.workloads import build_scenario

    CONC = {"chat": 4, "embed": 2, "flood": 2}
    SLO = {"chat": 0.5, "embed": 1.0, "flood": 5.0}

    def _sim(*, gateway, mem, flood_maxi, batch_limit):
        gw_kw = (dict(flood_rate=400.0, flood_burst=8.0,
                      max_inflight=4 * batch_limit, batch_share=0.25)
                 if gateway else {})
        wl = build_scenario("noisy_neighbor", gateway=gateway, seed=3,
                            duration_s=12.0, **gw_kw)
        store = ConfigStore()
        for p in wl.profiles:
            store.put(FunctionConfig(
                name=p.fn, arch="tiny_lm", concurrency=CONC[p.fn],
                cold_start_s=0.2, timeout_s=8.0,
                idle_timeout_s=1.0 if p.fn == "flood" else 10.0,
                max_instances_per_worker=(flood_maxi if p.fn == "flood"
                                          else 8)))
        sim = Simulator(build_pool(1, 2, leaf_policy="warm_least_loaded",
                                   inner_policy="round_robin"),
                        store, SyntheticServiceModel(seed=2, fail_rate=0.0),
                        seed=11, hedge_after_s=0.6, worker_memory_mb=mem)
        sim.load(wl)
        return sim

    rigs = {"noisy": dict(mem=1536, flood_maxi=1, batch_limit=6),
            "pinned": dict(mem=2048, flood_maxi=8, batch_limit=5)}
    for rig, kw in rigs.items():
        for gateway in (False, True):
            sim = _sim(gateway=gateway, **kw)
            t0 = time.perf_counter()
            results = sim.run()
            wall = time.perf_counter() - t0
            s = summarize(results)
            parts = []
            for fn, slo in sorted(SLO.items()):
                lat = sorted(r.latency for r in results
                             if r.fn == fn and r.ok)
                p95 = lat[int(0.95 * len(lat))] if lat else float("nan")
                parts.append(f"{fn}={p95 * 1e3:.0f}ms")
            shed = sim.gateway.shed_total if sim.gateway is not None else 0
            _row(f"gateway_{rig}_{'on' if gateway else 'off'}",
                 1e6 * s["p95"],
                 f"goodput={s['goodput']:.1f};ok={s['ok']};"
                 f"p95={','.join(parts)};hedges={sim.hedges_seen};"
                 f"shed={shed};sim_wall_s={wall:.1f}",
                 goodput=s["goodput"], ok=s["ok"],
                 hedges=sim.hedges_seen, shed=shed)


def bench_workflows():
    """ISSUE-7 acceptance probe: DAG workflows (`ml_pipeline` chain +
    conditional branch, `etl_fanout` map-reduce) routed stage-blind
    (`deadline_aware`) vs DAG-aware (`workflow_aware`) on identical
    fixed trees — equal worker-seconds, so the end-to-end workflow p95
    delta is routing-only. The acceptance shape (tests/test_workflows.py):
    eager critical-path cold starts + affinity tie-break + sibling
    waterfill beat the stage-blind baseline on both scenarios."""
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import Simulator, SyntheticServiceModel
    from repro.workloads import (build_scenario, install_demo_configs,
                                 summarize_workflows)

    for scen in ("ml_pipeline", "etl_fanout"):
        for policy in ("deadline_aware", "workflow_aware"):
            wl = build_scenario(scen, duration_s=40.0, seed=13)
            store = ConfigStore()
            install_demo_configs(store, wl)
            sim = Simulator(build_tree(8, fanout=4, leaf_policy=policy,
                                       inner_policy=policy),
                            store, SyntheticServiceModel(seed=2), seed=11)
            sim.load(wl)
            t0 = time.perf_counter()
            sim.run()
            wall = time.perf_counter() - t0
            s = summarize_workflows(sim.workflow_results)
            eng = sim.workflows
            _row(f"workflow_{scen}_{policy}", 1e6 * s["p95"],
                 f"n={s['n']};tasks={eng.tasks_submitted};"
                 f"fail={s['fail_rate']:.4f};p50_ms={s['p50']*1e3:.1f};"
                 f"p99_ms={s['p99']*1e3:.1f};prewarms={eng.prewarms};"
                 f"sim_wall_s={wall:.1f}")


def bench_event_backends():
    """ISSUE-5 acceptance probe: the standalone `EventEngine` under a
    ≥10M-request event stream, once per registered backend.

    The stream is the simulator's real shape: per-tenant Poisson arrival
    streams bulk-loaded *stream by stream* (the Azure-trace multi-tenant
    ingest order — globally near-random in time, which is a binary
    heap's honest worst case: every sift walks ~log(10M) cache-hostile
    levels of a shuffled gigabyte-scale array), then drained while each
    arrival spawns the operational chain (enqueue +hop, finish +service,
    idle_check +30s). The sharded calendar queue seals the bulk load
    into sorted per-bucket runs and keeps dynamic events in small
    cache-resident bucket heaps, so its advantage grows with pending-set
    scale. Both backends must pop the identical (t, seq) stream — the
    probe cross-checks a sampled hash.

    End-to-end *simulator* events/s gains are smaller (~1.1-1.2x at 10M:
    routing/dispatch/service handlers dominate the per-event cost and
    are backend-independent); set EVENT_BACKEND_SIM_PROBE=1 to measure
    and record that full-sim probe too (~25 min extra).
    EVENT_BACKEND_PROBE_S (default 505) scales the horizon: 505 s ×
    2000 streams × 10 rps ≈ 10.1M requests ≈ 40M events."""
    import random as _random

    from repro.core.events import EventEngine

    hop_s, idle_s = 0.0005, 30.0

    def engine_probe(backend, streams, duration_s):
        eng = EventEngine(backend)
        n = 0
        t0 = time.perf_counter()
        for s in range(streams):           # tenant-by-tenant bulk ingest
            srng = _random.Random(100 + s)
            t = 0.0
            while True:
                t += srng.expovariate(10.0)
                if t >= duration_s:
                    break
                eng.push(t, "arrival", None)
                n += 1
        t_load = time.perf_counter() - t0
        drng = _random.Random(7)
        sample = 0
        pops = 0
        t0 = time.perf_counter()
        while True:
            e = eng.pop()
            if e is None:
                break
            pops += 1
            kind = e[2]
            if kind == "arrival":
                eng.push(e[0] + hop_s, "enqueue", None)
            elif kind == "enqueue":
                eng.push(e[0] + 0.004 + 0.01 * drng.random(), "finish", None)
            elif kind == "finish":
                eng.push(e[0] + idle_s, "idle_check", None)
            if not pops % 997:             # cheap cross-backend witness
                sample ^= hash((e[0], e[1]))
        wall = time.perf_counter() - t0
        return n, pops, t_load, wall, sample

    dur = float(os.environ.get("EVENT_BACKEND_PROBE_S", "505"))
    rates, hashes, scalar_e2e = {}, {}, {}
    for backend in ("single_heap", "sharded"):
        engine_probe(backend, 200, 20.0)   # warmup: page/arena state
        n, pops, t_load, wall, sample = engine_probe(backend, 2000, dur)
        if dur >= 505:
            assert n >= 10_000_000, \
                f"acceptance probe must drive >=10M requests, got {n}"
        rates[backend] = pops / wall
        hashes[backend] = sample
        # load_s covers scalar generation + ingest interleaved, so
        # end_to_end is the full generate-and-simulate rate the bulk
        # pipeline below is gated against
        scalar_e2e[backend] = pops / (t_load + wall)
        _row(f"event_engine_{backend}", 1e6 * wall / n,
             f"requests={n};events={pops};events_per_s={pops / wall:.0f};"
             f"end_to_end_events_per_s={pops / (t_load + wall):.0f};"
             f"load_s={t_load:.1f};run_s={wall:.1f}",
             requests=n, events=pops, events_per_s=pops / wall,
             end_to_end_events_per_s=pops / (t_load + wall))
    assert hashes["sharded"] == hashes["single_heap"], \
        "backends popped different (t, seq) streams"
    _row("event_engine_speedup", 0.0,
         f"sharded_over_single_heap="
         f"{rates['sharded'] / rates['single_heap']:.2f}x",
         sharded_over_single_heap=rates["sharded"] / rates["single_heap"])

    # ---- ISSUE-8 bulk mode: generate_bulk + push_bulk + pop_batch,
    # the same 10M-request Azure-style probe end to end through the
    # vectorized pipeline (own numpy determinism contract, so the
    # cross-backend hash witness is checked *within* the bulk mode)
    from repro.workloads import (FunctionProfile, MixedWorkload,
                                 PoissonArrivals, SizeDist)

    def make_streams(streams, duration_s):
        profile = [FunctionProfile("fn", size=SizeDist.const(16))]
        return [MixedWorkload(PoissonArrivals(10.0), profile,
                              duration_s=duration_s, seed=100 + s)
                for s in range(streams)]

    def bulk_probe(backend, streams, duration_s):
        eng = EventEngine(backend)
        t0 = time.perf_counter()
        arrival_runs = [wl.generate_bulk().arrival_t
                        for wl in make_streams(streams, duration_s)]
        n = sum(len(r) for r in arrival_runs)
        t_gen = time.perf_counter() - t0
        t0 = time.perf_counter()
        for times in arrival_runs:         # tenant-by-tenant bulk ingest
            eng.push_bulk(times, "arrival", None)
        t_load = time.perf_counter() - t0
        drng = _random.Random(7).random
        sample = 0
        pops = 0
        pop_batch = eng.pop_batch
        push_bulk = eng.push_bulk
        t0 = time.perf_counter()
        while True:
            batch = pop_batch(8192)
            if not batch:
                break
            # one comprehension pass per kind beats a per-event dispatch
            # loop; batch partitions are backend-identical (greedy
            # pop_batch contract), so the strided witness below samples
            # the same global every-997th events on every backend
            enq = [e[0] + hop_s for e in batch if e[2] == "arrival"]
            fin = [e[0] + 0.004 + 0.01 * drng()
                   for e in batch if e[2] == "enqueue"]
            idle = [e[0] + idle_s for e in batch if e[2] == "finish"]
            for e in batch[(996 - pops) % 997::997]:
                sample ^= hash((e[0], e[1]))
            pops += len(batch)
            if enq:
                push_bulk(enq, "enqueue", None)
            if fin:
                push_bulk(fin, "finish", None)
            if idle:
                push_bulk(idle, "idle_check", None)
        wall = time.perf_counter() - t0
        return n, pops, t_gen, t_load, wall, sample

    # scalar generation baseline at the same request volume: the real
    # per-request path sim.load walks (one Mersenne draw chain + one
    # Request object per arrival)
    t0 = time.perf_counter()
    n_scalar_gen = 0
    for wl in make_streams(2000, dur):
        for _ in wl.requests():
            n_scalar_gen += 1
    scalar_gen_rps = n_scalar_gen / (time.perf_counter() - t0)

    bulk_e2e, bulk_hashes = {}, {}
    gen_rps = 0.0
    for backend in ("single_heap", "sharded"):
        bulk_probe(backend, 200, 20.0)     # warmup
        n, pops, t_gen, t_load, wall, sample = bulk_probe(backend, 2000,
                                                          dur)
        gen_rps = n / t_gen
        bulk_e2e[backend] = pops / (t_gen + t_load + wall)
        bulk_hashes[backend] = sample
        _row(f"event_engine_bulk_{backend}",
             1e6 * (t_gen + t_load + wall) / n,
             f"requests={n};events={pops};gen_s={t_gen:.1f};"
             f"gen_req_per_s={n / t_gen:.0f};load_s={t_load:.1f};"
             f"run_s={wall:.1f};events_per_s={pops / wall:.0f};"
             f"end_to_end_events_per_s={bulk_e2e[backend]:.0f}",
             requests=n, events=pops, events_per_s=pops / wall,
             end_to_end_events_per_s=bulk_e2e[backend])
    assert bulk_hashes["sharded"] == bulk_hashes["single_heap"], \
        "bulk pipeline popped different (t, seq) streams across backends"
    gen_speedup = gen_rps / scalar_gen_rps
    e2e_speedup = bulk_e2e["sharded"] / scalar_e2e["sharded"]
    _row("event_engine_bulk_speedup", 0.0,
         f"generation_bulk_over_scalar={gen_speedup:.1f}x;"
         f"end_to_end_bulk_sharded_over_scalar_sharded="
         f"{e2e_speedup:.2f}x;"
         f"end_to_end_bulk_sharded_over_scalar_single_heap="
         f"{bulk_e2e['sharded'] / scalar_e2e['single_heap']:.2f}x;"
         f"scalar_gen_req_per_s={scalar_gen_rps:.0f}",
         generation_bulk_over_scalar=gen_speedup,
         end_to_end_bulk_over_scalar=e2e_speedup)
    if dur >= 505:                         # ISSUE-8 acceptance gates
        assert gen_speedup >= 10.0, \
            f"bulk generation {gen_speedup:.1f}x < 10x scalar"
        assert e2e_speedup >= 3.0, \
            f"bulk end-to-end {e2e_speedup:.2f}x < 3x scalar sharded"

    if not os.environ.get("EVENT_BACKEND_SIM_PROBE"):
        return

    # ---- optional end-to-end probe: the full simulator at ≥10M requests
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import Simulator, SyntheticServiceModel
    from repro.core.types import FunctionConfig
    from repro.workloads import (FunctionProfile, MixedWorkload,
                                 PoissonArrivals, SizeDist)

    def sim_probe(backend, duration_s):
        store = ConfigStore()
        store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=16,
                                 cold_start_s=0.05, idle_timeout_s=30.0,
                                 max_instances_per_worker=8))
        wl = MixedWorkload(PoissonArrivals(20000.0),
                           [FunctionProfile("fn", size=SizeDist.const(24))],
                           duration_s=duration_s, seed=3)
        sim = Simulator(build_tree(64, fanout=8, leaf_policy="random"),
                        store, SyntheticServiceModel(seed=2), seed=7,
                        event_backend=backend, collect_telemetry=False)
        t0 = time.perf_counter()
        n = sim.load(wl)
        t_load = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim.run()
        wall = time.perf_counter() - t0
        return n, sim, t_load, wall

    sim_rates = {}
    for backend in ("single_heap", "sharded"):
        sim_probe(backend, 20.0)           # warmup
        n, sim, t_load, wall = sim_probe(backend, dur)
        fails = sum(not r.ok for r in sim.results)
        sim_rates[backend] = sim.events_processed / wall
        _row(f"event_backend_sim_{backend}", 1e6 * wall / n,
             f"requests={n};events={sim.events_processed};"
             f"events_per_s={sim.events_processed / wall:.0f};"
             f"load_s={t_load:.1f};run_s={wall:.1f};fails={fails}")
    _row("event_backend_sim_speedup", 0.0,
         f"sharded_over_single_heap="
         f"{sim_rates['sharded'] / sim_rates['single_heap']:.2f}x")


def bench_parallel_sim():
    """ISSUE-10 acceptance probe: partitioned simulation (repro.parallel)
    vs the best serial pipeline on the same ≥10M-request Azure-style
    multi-tenant workload.

    The workload is 200 per-tenant Poisson streams with heterogeneous
    request-size mixes and disjoint rid ranges — exactly the shape
    ``azure_trace_streams`` produces and ``partition_streams`` buckets.
    The serial baseline is the strongest single-process pipeline the
    repo has (sharded calendar backend + vectorized ``load_bulk`` + a
    ``ResultSink`` so 10M rows never materialize); the partitioned run
    forks 8 workers, each owning its crc32 bucket of streams and an
    8-worker subtree of the same 64-worker fleet, free-running on the
    uncoupled fast path with summary collection. Parallel events/s is
    charged the *entire* ``run_partitioned`` wall (fork + in-worker
    generation + merge); serial events/s excludes its own generation —
    the comparison is conservative toward serial.

    Acceptance (ISSUE 10): ≥ 4x merged events/s over serial, asserted
    here when the probe is full-size and the machine has ≥ 12 cores;
    CI gates ≥ 2.5x on its 4-vCPU runner from the JSON metrics. A
    small barrier-coupled run (global ``max_inflight`` re-apportioned
    at conservative-lookahead windows) rides along to keep the
    windowed regime measured.

    PARALLEL_SIM_PROBE_S (default 505) scales the horizon: 505 s ×
    200 streams × 100 rps ≈ 10.1M requests."""
    from repro.core.config_store import ConfigStore
    from repro.core.gateway import GatewayConfig
    from repro.core.router import build_tree
    from repro.core.simulator import Simulator, SyntheticServiceModel
    from repro.core.types import FunctionConfig
    from repro.parallel import partition_streams, run_partitioned
    from repro.parallel.partition import maybe_attach_sink
    from repro.workloads import (FunctionProfile, MixedWorkload,
                                 PoissonArrivals, SizeDist)

    dur = float(os.environ.get("PARALLEL_SIM_PROBE_S", "505"))
    n_streams, rps, K = 200, 100.0, 8
    ncpu = os.cpu_count() or 1
    SIZES = [SizeDist.const(16), SizeDist.const(24),
             SizeDist.uniform(8, 48), SizeDist.lognormal(24, 0.5)]

    def make_streams():
        return [MixedWorkload(PoissonArrivals(rps),
                              [FunctionProfile(f"t{s:03d}",
                                               size=SIZES[s % len(SIZES)])],
                              duration_s=dur, seed=100 + s,
                              rid_base=s * 100_000_000)
                for s in range(n_streams)]

    def store_for(fns):
        store = ConfigStore()
        for fn in fns:
            store.put(FunctionConfig(name=fn, arch="tiny_lm", concurrency=16,
                                     cold_start_s=0.05, idle_timeout_s=30.0,
                                     max_instances_per_worker=8))
        return store

    # 200 tenants land on every node under random routing, so nodes must
    # hold >200 warm instances or the default 16-slot cap thrashes cold
    # starts and everything queue-times-out
    serial = Simulator(build_tree(64, fanout=8, leaf_policy="random"),
                       store_for(f"t{s:03d}" for s in range(n_streams)),
                       SyntheticServiceModel(seed=2), seed=7,
                       event_backend="sharded", collect_telemetry=False,
                       worker_capacity_slots=256)
    sink = maybe_attach_sink(serial)
    t0 = time.perf_counter()
    n = sum(serial.load_bulk(wl) for wl in make_streams())
    t_load = time.perf_counter() - t0
    if dur >= 505:
        assert n >= 10_000_000, \
            f"acceptance probe must drive >=10M requests, got {n}"
    t0 = time.perf_counter()
    serial.run()
    t_run = time.perf_counter() - t0
    serial_eps = serial.events_processed / t_run
    _row("parallel_sim_serial", 1e6 * t_run / n,
         f"requests={n};events={serial.events_processed};"
         f"events_per_s={serial_eps:.0f};load_s={t_load:.1f};"
         f"run_s={t_run:.1f};ok={sink.part()['ok']}",
         requests=n, events=serial.events_processed,
         events_per_s=serial_eps)

    def build(k, nparts):
        mine = partition_streams(make_streams(), nparts)[k]
        sim = Simulator(build_tree(8, fanout=8, leaf_policy="random",
                                   prefix=f"p{k}"),
                        store_for(s.profiles[0].fn for s in mine),
                        SyntheticServiceModel(seed=2), seed=7,
                        event_backend="sharded", collect_telemetry=False,
                        worker_capacity_slots=256)
        for wl in mine:
            sim.load_bulk(wl)
        return sim

    t0 = time.perf_counter()
    merged = run_partitioned(build, K, collect="summary")
    wall = time.perf_counter() - t0
    ev = merged.counters["events_processed"]
    assert merged.counters["results"] == n, \
        (merged.counters["results"], n)
    par_eps = ev / wall
    speedup = par_eps / serial_eps
    _row("parallel_sim_partitioned", 1e6 * wall / n,
         f"requests={n};events={ev};partitions={K};mode={merged.mode};"
         f"events_per_s={par_eps:.0f};wall_s={wall:.1f};"
         f"ok={merged.summary()['ok']}",
         requests=n, events=ev, partitions=K, events_per_s=par_eps)
    _row("parallel_sim_speedup", 0.0,
         f"partitioned_over_serial={speedup:.2f}x;ncpu={ncpu}",
         partitioned_over_serial=speedup, ncpu=ncpu)
    if dur >= 505 and ncpu >= 12:
        assert speedup >= 4.0, \
            f"partitioned {speedup:.2f}x < 4x serial events/s"

    # barrier-coupled regime: partition-local gateways as shards of one
    # platform-wide ceiling, re-apportioned each conservative window
    def build_coupled(k, nparts):
        streams = [MixedWorkload(PoissonArrivals(200.0),
                                 [FunctionProfile(f"g{j}")],
                                 duration_s=4.0, seed=j,
                                 rid_base=j * 1_000_000)
                   for j in range(16)]
        mine = partition_streams(streams, nparts)[k]
        sim = Simulator(build_tree(4, fanout=4, leaf_policy="random",
                                   prefix=f"q{k}"),
                        store_for(s.profiles[0].fn for s in mine),
                        SyntheticServiceModel(seed=2), seed=7,
                        gateway=GatewayConfig(max_inflight=64),
                        collect_telemetry=False,
                        worker_capacity_slots=64)
        for wl in mine:
            sim.load_bulk(wl)
        return sim

    t0 = time.perf_counter()
    coupled = run_partitioned(build_coupled, 4, max_inflight=256,
                              collect="summary")
    wall = time.perf_counter() - t0
    _row("parallel_sim_coupled", 1e6 * wall
         / max(coupled.counters["results"], 1),
         f"requests={coupled.counters['results']};"
         f"barriers={len(coupled.barriers)};window_s={coupled.window_s};"
         f"admitted={coupled.counters['gw_admitted']};"
         f"shed={coupled.counters['gw_shed']};wall_s={wall:.1f}",
         barriers=len(coupled.barriers),
         admitted=coupled.counters["gw_admitted"],
         shed=coupled.counters["gw_shed"])


def bench_sim_throughput():
    from repro.core.config_store import ConfigStore
    from repro.core.router import build_tree
    from repro.core.simulator import (Simulator, SyntheticServiceModel,
                                      poisson_load)
    from repro.core.types import FunctionConfig
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch="tiny_lm", concurrency=4,
                             cold_start_s=0.1))
    sim = Simulator(build_tree(256, fanout=16), store,
                    SyntheticServiceModel(seed=2), seed=7)
    n = poisson_load(sim, fn="fn", rps=5000, duration_s=10, seed=3)
    t0 = time.perf_counter()
    sim.run()
    wall = time.perf_counter() - t0
    _row("sim_throughput", 1e6 * wall / n,
         f"requests={n};workers=256;req_per_s={n/wall:.0f}")


def roofline_table():
    import json
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")
    if not os.path.isdir(art):
        _row("roofline_table", 0.0, "no dryrun artifacts; run repro.launch.sweep")
        return
    for f in sorted(os.listdir(art)):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(art, f)) as fh:
            d = json.load(fh)
        if d.get("status") != "ok":
            continue
        r = d["report"]
        _row(f"roofline_{d['arch']}_{d['shape']}_{d['mesh']}",
             1e6 * max(r["t_compute"], r["t_memory"], r["t_collective"]),
             f"bottleneck={r['bottleneck']};useful={r['useful_flops_ratio']:.3f};"
             f"frac={r['roofline_fraction']:.4f};peak_gib={r['mem']['peak_gib']:.1f};"
             f"fits={d.get('fits')}")


BENCHES = [bench_tree_scaling, bench_lb_policies, bench_concurrency,
           bench_emulation, bench_serving_engine, bench_kernels,
           bench_workload_scenarios, bench_workload_generation,
           bench_autoscaler_scenarios, bench_placement,
           bench_fault_scenarios, bench_gateway, bench_workflows,
           bench_event_backends, bench_parallel_sim,
           bench_sim_throughput, roofline_table]


def _usage() -> str:
    return ("usage: python benchmarks/run.py [probe-substring]\n"
            "probes: " + " ".join(b.__name__.removeprefix("bench_")
                                  for b in BENCHES))


def main(argv=None) -> None:
    # strict arg handling: a flag-like or unmatched argument used to
    # fall through as a probe name, run zero probes, and write a junk
    # artifact (results_--help.json) — reject it loudly instead
    argv = sys.argv[1:] if argv is None else argv
    only = None
    if argv:
        if argv[0] in ("-h", "--help"):
            print(_usage())
            return
        if len(argv) > 1 or argv[0].startswith("-"):
            print(f"unexpected arguments: {' '.join(argv)}\n{_usage()}",
                  file=sys.stderr)
            sys.exit(2)
        only = argv[0]
        if not any(only in b.__name__ for b in BENCHES):
            print(f"no benchmark matches {only!r}\n{_usage()}",
                  file=sys.stderr)
            sys.exit(2)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for b in BENCHES:
        if only and only not in b.__name__:
            continue
        try:
            b()
        except Exception as e:  # record the failure, run the other probes
            traceback.print_exc()
            _row(b.__name__ + "_ERROR", 0.0, repr(e)[:120])
            failed.append(b.__name__)
    os.makedirs(OUT_DIR, exist_ok=True)
    # REPRO_EVENT_BACKEND suffixes the artifact so CI's per-backend runs
    # of the same bench don't overwrite each other
    backend = os.environ.get("REPRO_EVENT_BACKEND")
    suffix = (f"_{only}" if only else "") + (f"_{backend}" if backend else "")
    out = os.path.join(OUT_DIR, f"results{suffix}.json")
    with open(out, "w") as fh:
        json.dump({"filter": only, "backend": backend, "rows": ROWS}, fh,
                  indent=1)
    if failed:
        print(f"probes raised: {' '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
