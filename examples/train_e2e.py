"""End-to-end training driver: a ~100M-parameter assigned-architecture LM
trained for a few hundred steps through the FULL production stack —
data pipeline -> jit'd train step (microbatched AdamW) -> fault-tolerant
TrainLoop with async checkpointing, straggler monitor and (optional)
simulated mid-run crash + restart.

    PYTHONPATH=src python examples/train_e2e.py \
        --arch starcoder2-3b --steps 200 [--crash-at 120]

The default config is the assigned starcoder2-3b family scaled to ~100M
params (d=768, 8 layers) with seq 256 / batch 8 so a few hundred steps
fit CPU minutes; the loss curve is printed and must decrease.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--d-ff", type=int, default=3072)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="simulate a hard step failure at this step")
    args = ap.parse_args()

    from repro.checkpoint import Checkpointer, latest_step
    from repro.configs import get_config
    from repro.data import TokenStream, make_batch_iterator
    from repro.launch.train import init_train_state, make_train_step
    from repro.models.zoo import get_model
    from repro.optim.adamw import cosine_schedule
    from repro.runtime import TrainLoop

    cfg = get_config(args.arch).reduced(
        n_layers=args.layers, d_model=args.d_model, n_heads=args.heads,
        d_ff=args.d_ff, vocab=8192)
    import dataclasses
    cfg = dataclasses.replace(cfg, microbatch=2, remat=True)
    bundle = get_model(cfg)
    params, opt = init_train_state(bundle, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} (reduced family) params={n_params/1e6:.1f}M "
          f"seq={args.seq} batch={args.batch}")

    stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=0)
    step_jit = jax.jit(make_train_step(
        bundle, cosine_schedule(3e-4, 20, args.steps)), donate_argnums=(0, 1))

    ck = Checkpointer(args.ckpt_dir, keep=2)
    loop = TrainLoop(
        step_fn=lambda p, o, b: step_jit(p, o, b),
        batch_iter_fn=lambda s: make_batch_iterator(stream, start_step=s),
        ckpt=ck, ckpt_every=args.ckpt_every)

    injector = None
    if args.crash_at >= 0:
        crashed = {"n": 0}

        def injector(step, attempt):
            if step == args.crash_at and crashed["n"] < 3:
                crashed["n"] += 1
                raise RuntimeError("injected failure")

    t0 = time.time()
    start = latest_step(args.ckpt_dir) or 0
    if start:
        start, (params, opt) = ck.restore(like=(params, opt))
        print(f"resuming from checkpoint step {start}")
    out = loop.run(params, opt, n_steps=args.steps, start_step=start,
                   fail_injector=injector)
    dt = time.time() - t0

    hist = out["history"]
    k = max(5, len(hist) // 20)
    first, last = float(np.mean(hist[:k])), float(np.mean(hist[-k:]))
    print(f"\nsteps {len(hist)} in {dt:.0f}s "
          f"({dt/max(len(hist),1):.2f}s/step)")
    print(f"loss first-{k} avg {first:.3f} -> last-{k} avg {last:.3f}")
    print(f"stragglers flagged: {len(out['stragglers'])}")
    assert last < first - 0.3, "loss must decrease"
    print("OK: end-to-end training through the production stack")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
