"""Serving driver: prefill a batch of requests, then decode autoregressively.

CPU-runnable with --reduced; the same jitted step functions are what the
dry-run lowers for the production mesh. The decode loop is scan-compiled
through the round engine's `scan_steps` (core/engine.py) — the whole
generation is ONE dispatch instead of one per token; `--no-scan` keeps the
legacy per-token loop.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_architectures
from repro.core.engine import scan_steps
from repro.models import Transformer
from repro.utils import get_logger
from repro.utils.compile_cache import use_compile_cache

log = get_logger("serve")


def serve(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Transformer(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)

    B = args.batch
    cache_len = args.prompt_len + args.gen
    window = cfg.sliding_window if args.long_context else None

    prompts = jax.random.randint(
        rng, (B, args.prompt_len), 0, cfg.vocab_size, dtype=jnp.int32
    )

    prefill = jax.jit(
        lambda p, t: model.prefill(p, tokens=t, cache_len=cache_len, window=window)
    )

    t0 = time.time()
    logits, cache = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    if getattr(args, "no_scan", False):
        decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos, window=window)
        )
        out = [tokens]
        t0 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            logits, cache = decode(params, cache, tokens, pos)
            tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out.append(tokens)
        jax.block_until_ready(out[-1])
        t_decode = time.time() - t0
        gen = np.asarray(jnp.concatenate(out, axis=1))
    else:
        # scan-compiled decode: the whole generation is one dispatch
        def step(carry, p):
            c, t, pos = carry
            logits, c = model.decode_step(p, c, t, pos, window=window)
            t = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            return (c, t, pos + 1), t

        run = scan_steps(step, args.gen - 1)
        carry0 = (cache, tokens, jnp.asarray(args.prompt_len, jnp.int32))
        t0 = time.time()
        (cache, _, _), rest = run(carry0, params)
        jax.block_until_ready(rest)
        t_decode = time.time() - t0
        # rest: (gen-1, B, 1) -> (B, gen-1); prepend the prefill's argmax
        gen = np.asarray(
            jnp.concatenate([tokens, jnp.swapaxes(rest[..., 0], 0, 1)], axis=1)
        )
    log.info("prefill %.3fs (%d tokens)  decode %.3fs (%.1f tok/s/req)",
             t_prefill, B * args.prompt_len, t_decode,
             (args.gen - 1) / max(t_decode, 1e-9))
    log.info("generated[0,:16] = %s", gen[0, :16].tolist())
    return gen


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_architectures(), required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--long-context", action="store_true")
    ap.add_argument("--no-scan", action="store_true",
                    help="legacy per-token decode dispatch (debugging)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    serve(args)


if __name__ == "__main__":
    main()
