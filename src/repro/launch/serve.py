"""Serving launcher: batched decode with the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --requests 16 [--reduced]
"""

from __future__ import annotations

import argparse
import time

import jax

from ..models import build_model, get_config
from ..serve import ServeEngine
from .compile_cache import use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-context", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=args.batch,
                      max_context=args.max_context, eos_token=-1,
                      temperature=args.temperature)
    rng = jax.random.PRNGKey(7)
    t0 = time.time()
    for i in range(args.requests):
        prompt = [int(x) for x in jax.random.randint(
            jax.random.fold_in(rng, i), (4,), 0, cfg.vocab)]
        eng.submit(prompt, max_new_tokens=args.max_new)
    results = eng.run(max_steps=100_000)
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in results)
    print(f"[launch.serve] {len(results)} requests, {toks} tokens, "
          f"{dt:.2f}s, {toks / max(dt, 1e-9):.1f} tok/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
