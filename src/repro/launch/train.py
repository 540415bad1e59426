"""Training driver.

Two modes:
  * CPU / small-scale (default): actually trains a reduced or full config on
    the local devices — used by examples/train_lm.py for the end-to-end
    ~100M-param run.
  * --lower-only: builds the production-mesh train step exactly like the
    dry-run (for launcher parity checks).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.models import model as mdl
from repro.train import checkpoint as ckpt
from repro.train.lm_data import MarkovLM
from repro.train.optim import AdamW, cosine_schedule


def train_loop(cfg, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
               seed: int = 0, log_every: int = 20, ckpt_path=None,
               moe_mode: str = "dense", d_model_vocab_cap: int | None = 8192):
    vocab = min(cfg.vocab, d_model_vocab_cap or cfg.vocab)
    data = MarkovLM(vocab, seed=seed)
    params = mdl.init_params(jax.random.PRNGKey(seed), cfg)
    opt = AdamW(lr=cosine_schedule(lr, warmup=max(10, steps // 20),
                                   total=steps),
                weight_decay=0.1, clip_norm=1.0)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch_):
        loss, grads = jax.value_and_grad(mdl.loss_fn)(
            params, cfg, batch_, moe_mode=moe_mode, q_chunk=min(512, seq))
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    it = data.batches(batch, seq)
    hist = []
    t0 = time.time()
    for s in range(steps):
        b = next(it)
        b = {k: jnp.asarray(np.minimum(v, cfg.vocab - 1)) for k, v in b.items()}
        params, opt_state, loss = step(params, opt_state, b)
        hist.append(float(loss))
        if s % log_every == 0 or s == steps - 1:
            print(f"step {s:5d}  loss {hist[-1]:.4f}  "
                  f"({(time.time()-t0)/(s+1):.2f}s/step)")
    if ckpt_path:
        ckpt.save(ckpt_path, {"params": params, "step": steps})
        print("saved checkpoint →", ckpt_path)
    return params, hist


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    _, hist = train_loop(cfg, steps=args.steps, batch=args.batch,
                         seq=args.seq, lr=args.lr, ckpt_path=args.ckpt)
    print(f"final loss {hist[-1]:.4f} (start {hist[0]:.4f})")


if __name__ == "__main__":
    main()
