"""Readings that a cell's output limits are set from, all in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,... --control-seeds 1,2,3

For each seed: the cell's own set-up with that seed's weights and traffic, a
short window at the cell's own load, and the comparison a run makes (the
sound reading). For each control seed also the controls: the reference in
int8 and in fp8 (e4m3) put in the engine's place, each read on the same
prompts and tokens as the gap of the token it puts first. One JSON line per
seed, with the verdict of the cell's committed limits (``limits/<cell>.json``)
on the sound reading and on each control's. The limit of ``max_logit_gap``
lies above every sound reading and below every control reading. Needs a
TPU, like ``run.py``; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from run import CACHE, ROOT, cell_of, require_chips, spec  # noqa: I001


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--traffic", help="another mix in the cell's place")
    args = ap.parse_args(argv)
    cell, cfg = cell_of(spec(), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    require_chips(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    from bench import check, harness, traffic
    enable_compile_cache()
    mix = traffic.load(args.traffic or cell["traffic"])
    limits = check.limits(cell["name"])
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        h = harness.Harness(cfg, mix, seed)
        h.warm_up()
        h.run(time.perf_counter(), args.seconds)
        h.drain()
        finished = [r for r in h.reqs if r.served is not None]
        h.free()
        del h
        gc.collect()
        nums = check.numbers(cfg, seed, finished, mix.output_tokens)
        line = {"seed": seed, "finished": len(finished), **nums,
                "correct": check.verdict(nums, limits)}
        if seed in controls:
            picked = check.sample(finished, seed)
            for quant in ("int8", "fp8"):
                gap = float(check.gaps(cfg, seed, picked, quant=quant).max())
                line[f"{quant}_max_logit_gap"] = gap
                line[f"{quant}_correct"] = check.verdict(
                    {**nums, "max_logit_gap": gap}, limits)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
