"""Serve a small LM with batched requests through the production engine.

    PYTHONPATH=src python examples/serve_lm.py [--arch qwen3-8b]

Uses the reduced (smoke) config so it runs on CPU; the serving path —
prefill into slots, fused batched decode, continuous admission — is the same
program the decode_* dry-run cells lower at production scale.
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.models.zoo import build
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    api = build(get_arch(args.arch).smoke)
    params = api.init(jax.random.PRNGKey(0))
    engine = ServeEngine(api, slots=args.slots, max_len=96)
    engine.load(params)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, api.cfg.vocab, size=8,
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=16,
                    temperature=0.0 if i % 2 == 0 else 0.8)
            for i in range(args.requests)]

    t0 = time.perf_counter()
    done = engine.generate(reqs)
    dt = time.perf_counter() - t0
    n_tokens = sum(len(r.generated) for r in done)
    print(f"{args.arch} (smoke config), {args.slots} slots: "
          f"served {len(done)} requests / {n_tokens} tokens "
          f"in {dt:.2f}s ({n_tokens / dt:.1f} tok/s on 1 CPU core)")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt {r.prompt[:6].tolist()}... -> "
              f"{r.generated}")


if __name__ == "__main__":
    main()
