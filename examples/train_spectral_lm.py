"""Train the paper's own architecture: fourier_lm — an FNet-style masked LM
whose token-mixing layer IS the area-efficient 2D FFT engine.

Defaults train a small model for a quick CPU run; --full trains the ~100M
configuration for a few hundred steps (the assignment's end-to-end driver;
expect hours on this 1-core container — the small run demonstrates the
identical code path).

  PYTHONPATH=src python examples/train_spectral_lm.py --steps 120
  PYTHONPATH=src python examples/train_spectral_lm.py --full --steps 300
"""

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.data.pipeline import make_batch
from repro.models.build import build
from repro.train.loop import TrainLoop
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="~100M-param config (12L x 512 x 32768 vocab)")
    ap.add_argument("--ckpt", default="/tmp/fourier_lm_ckpt")
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    args = ap.parse_args()

    cfg = get_config("fourier_lm")
    if not args.full:
        cfg = cfg.scaled(n_layers=4, d_model=128, d_ff=512, vocab=2048,
                         remat=False, compute_dtype="float32")
    model = build(cfg)
    print(f"[spectral-lm] params={model.n_params/1e6:.1f}M "
          f"(mixing = Re(FFT2), variant={cfg.fft_variant})")

    loop = TrainLoop(
        model,
        ckpt_dir=args.ckpt,
        batch_fn=lambda s: make_batch(cfg, args.batch, args.seq, s),
        save_every=max(args.steps // 4, 10),
        peak_lr=args.peak_lr,
    )
    t0 = time.time()
    losses = loop.run(jax.random.PRNGKey(0), args.steps)
    dt = time.time() - t0
    steps = sorted(losses)
    k = max(len(steps) // 10, 1)
    first = float(np.mean([losses[s] for s in steps[:k]]))
    last = float(np.mean([losses[s] for s in steps[-k:]]))
    print(f"[spectral-lm] {len(steps)} steps in {dt:.1f}s; "
          f"masked-LM loss {first:.3f} -> {last:.3f}")
    if last >= first:
        raise SystemExit("loss did not decrease")
    print("[spectral-lm] OK — the paper's engine trains as an LM mixing layer")


if __name__ == "__main__":
    main()
