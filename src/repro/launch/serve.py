"""Serving launcher: pack a model offline, serve with token-level
continuous batching (freed slots are refilled mid-flight from the queue).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch bitnet-0.73b --reduced \
      --n-requests 8 --prompt-len 32 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.serving import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-0.73b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length; actual lengths are mixed "
                         "uniformly in [4, prompt-len]")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                          vocab_size=256)
    print(f"init + offline base-3 packing ({args.arch})...")
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    packed = transformer.pack_params(cfg, params)

    rng = np.random.default_rng(args.seed)
    plens = rng.integers(min(4, args.prompt_len), args.prompt_len + 1,
                         size=args.n_requests)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=int(plen)),
                    max_new_tokens=args.max_new)
            for plen in plens]
    eng = ServingEngine(cfg, packed, max_seq=args.prompt_len + args.max_new,
                        batch_slots=args.batch_slots)
    t0 = time.perf_counter()
    eng.run(reqs)
    wall = time.perf_counter() - t0
    total_new = sum(len(r.output) for r in reqs)
    ttfts = [r.ttft_s for r in reqs]
    print(f"served {len(reqs)} requests, {total_new} tokens in {wall:.2f}s "
          f"-> {total_new / wall:.1f} tok/s aggregate "
          f"({eng.stats['mid_flight_admissions']} mid-flight admissions)")
    print(f"TTFT: mean {np.mean(ttfts)*1e3:.0f}ms  "
          f"p90 {np.percentile(ttfts, 90)*1e3:.0f}ms")


if __name__ == "__main__":
    main()
