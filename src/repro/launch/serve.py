"""LM-ONLY batched serving driver: prefill a batch of prompts, then greedy
decode. Drives the language-model configs (``repro.configs``) exclusively —
it does NOT serve Tucker decompositions. For batched FastTucker inference
(the paper's workload: predict / reconstruct / top-k from trained factors)
use ``repro.launch.serve_tucker`` and the ``repro.serve`` engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b --reduced \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import steps as S
from repro.models import init_cache, init_model, unbox

log = logging.getLogger("repro.serve")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="LM prefill+decode serving (language-model configs "
                    "only). For batched FastTucker inference use "
                    "repro.launch.serve_tucker.")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    logging.basicConfig(level=logging.INFO)

    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")

    key = jax.random.PRNGKey(0)
    params = unbox(init_model(key, cfg))
    B = args.batch
    max_len = args.prompt_len + args.gen
    caches = init_cache(cfg, B, max_len, dtype=jnp.float32)

    prompts = jax.random.randint(key, (B, args.prompt_len), 0,
                                 cfg.vocab_size)
    prefill = jax.jit(S.make_prefill_step(cfg))
    decode = jax.jit(S.make_decode_step(cfg))

    t0 = time.time()
    last_logits, caches = prefill(params, {"tokens": prompts}, caches)
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]
    log.info("prefill %d×%d in %.2fs", B, args.prompt_len, time.time() - t0)

    out = [tok]
    index = jnp.asarray(args.prompt_len, jnp.int32)
    t1 = time.time()
    for _ in range(args.gen - 1):
        tok, caches, index = decode(params, caches, index, {"tokens": tok})
        out.append(tok)
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    dt = time.time() - t1
    log.info("decoded %d tokens/seq × %d seqs in %.2fs (%.1f tok/s)",
             args.gen, B, dt, B * (args.gen - 1) / max(dt, 1e-9))
    log.info("sample generation: %s", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
