"""Serve a small LM with batched requests through the slot-based
continuous-batching engine.

    PYTHONPATH=src python examples/serve_lm.py --arch olmo-1b --requests 6
"""

import argparse
import time

import numpy as np
import jax

from repro.configs import get_smoke
from repro.launch.api import get_api
from repro.launch.compile_cache import enable_compile_cache
from repro.models.module import init_params
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch)
    api = get_api(cfg)
    params = init_params(api.param_spec(), jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, slots=args.slots,
                         max_len=args.prompt_len + args.new_tokens + 4)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=args.new_tokens))
    finished = engine.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out_tokens) for r in finished)
    for r in sorted(finished, key=lambda r: r.uid):
        print(f"req {r.uid}: prompt[:4]={r.prompt[:4].tolist()} -> "
              f"out={r.out_tokens}")
    print(f"{len(finished)} requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/dt:.1f} tok/s on CPU, {args.slots} slots)")


if __name__ == "__main__":
    main()
