"""Serving launcher: spin up the batched engine on a reduced config and
stream a few requests through it.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.cache import use_compile_cache
from repro.models import lm
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_reduced(args.arch)
    params, buffers = lm.init(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(cfg, params, buffers,
                         max_batch=args.max_batch, max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=int(rng.integers(3, 10)))
        engine.submit(Request(uid=i, prompt=prompt.astype(np.int32),
                              max_tokens=args.max_tokens))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"{args.arch}: served {len(done)} requests / {toks} tokens "
          f"in {dt:.1f}s ({engine.ticks} ticks, batch {args.max_batch})")
    for r in done[:3]:
        print(f"  req {r.uid}: prompt[:4]={r.prompt[:4].tolist()} -> {r.generated}")


if __name__ == "__main__":
    main()
