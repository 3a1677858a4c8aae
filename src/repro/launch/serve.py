"""Serving driver: batched decode over the slot engine.

  PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --smoke \\
      --requests 6 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import Request, ServeEngine, temperature_sample, greedy_sample


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(args.seed))
    sampler = (greedy_sample if args.temperature == 0.0
               else temperature_sample(args.temperature))
    eng = ServeEngine(cfg, params, num_slots=args.slots,
                      capacity=args.capacity, sampler=sampler, seed=args.seed)

    rng = np.random.RandomState(args.seed)
    k = cfg.num_codebooks
    shape = (args.prompt_len, k) if k > 1 else (args.prompt_len,)
    reqs = [
        Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, shape).astype(np.int32),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    t0 = time.time()
    done = []
    pending = list(reqs)
    while pending or any(s is not None for s in eng.slots):
        if pending and eng.cache is None:
            admitted = eng.admit(pending)
            pending = pending[len(admitted):]
        eng.step()
        done.extend(eng.drain())
        if not pending and not any(s is not None for s in eng.slots):
            break
    dt = time.time() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/max(dt,1e-9):.1f} tok/s)")
    for r in done[:4]:
        print(f"  rid={r.rid}: {r.out_tokens[:8]}...")
    return done


if __name__ == "__main__":
    main()
