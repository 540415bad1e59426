"""Serving driver: prefill + batched decode for one pool model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_config
from repro.models import model as mdl
from repro.serve.kv_cache import extend_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only — no decode path")

    key = jax.random.PRNGKey(0)
    params = mdl.init_params(key, cfg)
    B, S, T = args.batch, args.prompt_len, args.new_tokens
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)

    t0 = time.time()
    logits, _, cache = mdl.forward(params, cfg, tokens=toks,
                                   logits_last_only=True, return_cache=True,
                                   q_chunk=min(512, S))
    cache = extend_cache(cache, S + T)
    print(f"prefill {B}×{S}: {time.time()-t0:.2f}s")

    step = jax.jit(lambda p, c, t, pos: mdl.decode_step(p, c, cfg, tokens=t,
                                                        pos=pos))
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for t in range(T):
        logits_t, cache = step(params, cache, tok, jnp.int32(S + t))
        tok = jnp.argmax(logits_t[:, 0], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    print(f"decode {T} tokens × {B} seqs: {dt:.2f}s "
          f"({B*T/dt:.1f} tok/s)")
    print("sample:", jnp.concatenate(out, 1)[0].tolist())


if __name__ == "__main__":
    main()
