"""Serving launcher: stand up the platform and drive it with a workload.

  PYTHONPATH=src python -m repro.launch.serve --workers 2 --requests 16 \
      --fn-arch tiny_lm --concurrency 4
"""
from __future__ import annotations

import argparse

from repro.core.config_store import ConfigStore, ImageRegistry
from repro.core.router import build_tree
from repro.core.simulator import summarize
from repro.core.types import FunctionConfig, Request
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import Engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--fn-arch", default="tiny_lm")
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--gen-tokens", type=int, default=6)
    ap.add_argument("--policy", default="least_loaded")
    args = ap.parse_args(argv)

    enable_compile_cache()
    store = ConfigStore()
    store.put(FunctionConfig(name="fn", arch=args.fn_arch,
                             concurrency=args.concurrency,
                             gen_tokens=args.gen_tokens))
    engine = Engine(build_tree(args.workers, fanout=4,
                               leaf_policy=args.policy),
                    store, ImageRegistry(), max_len=64)
    for i in range(args.requests):
        engine.submit(Request(fn="fn", arrival_t=0.0, size=8 + 8 * (i % 3)))
    res = engine.run()
    s = summarize(res)
    print(f"[serve] ok={s['ok']}/{s['n']} p50={s['p50']*1e3:.0f}ms "
          f"p99={s['p99']*1e3:.0f}ms cold_rate={s['cold_rate']:.2f}")
    return 0


if __name__ == "__main__":
    main()
