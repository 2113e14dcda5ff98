"""Serving launcher: batched greedy decoding with compiled prefill/decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
        --batch 4 --prompt-len 64 --gen 64
"""

from __future__ import annotations

import argparse
import time

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=64)
    args = ap.parse_args()

    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.launch.cache import init_compile_cache
    from repro.models import build
    from repro.serve.engine import ServeEngine

    print(f"[serve] compile cache: {init_compile_cache()}")
    cfg = configs.get(args.arch) if args.full else \
        configs.get_reduced(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.gen + 1)
    batch = model.dummy_batch(
        ShapeConfig("serve", args.prompt_len, args.batch, "train"))
    t0 = time.time()
    out = engine.generate(batch, steps=args.gen)
    out.block_until_ready()
    cold = time.time() - t0
    t0 = time.time()
    out = engine.generate(batch, steps=args.gen)
    out.block_until_ready()
    warm = time.time() - t0
    print(f"[serve] {args.arch}: batch={args.batch} gen={args.gen} "
          f"cold={cold:.2f}s warm={warm:.2f}s "
          f"({args.batch * args.gen / warm:,.0f} tok/s)")


if __name__ == "__main__":
    main()
