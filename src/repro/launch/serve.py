"""Serving launcher: batched generation through the ServingEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b \
        --reduced --requests 6 --max-new 16 [--quant-bits 8]

--reduced (the default) serves the architecture family cut to CPU
scale; --no-reduced serves it at its published widths, which needs an
accelerator (starcoder2-3b: about 6.4 GB of bf16 weights).
"""

from __future__ import annotations

import argparse
import time

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the architecture cut to CPU scale; "
                         "--no-reduced serves its published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant-bits", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    from ..configs import get_config
    from ..models.zoo import get_model
    from ..serving import ServingEngine
    from ..serving.engine import Request

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    eng = ServingEngine(bundle, batch_size=args.batch_size,
                        temperature=args.temperature,
                        quant_bits=args.quant_bits)
    eng.load(params)

    reqs = [Request(rid=i, prompt=[(7 * i + j) % cfg.vocab
                                   for j in range(3 + i % 4)],
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    results = eng.serve(reqs)
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"req {rid}: {results[rid]}")
    print(f"\n{toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, quant={args.quant_bits or 'fp'})")


if __name__ == "__main__":
    main()
