"""Serving driver: batched prefill + greedy decode over the shortcut or
paged KV path, with the version-gated async maintenance manager.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --reduced \
      --batch 4 --prompt-len 32 --gen 16 --path shortcut
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get
from repro.kvcache import paged_cache as pc
from repro.models import model as M
from repro.runtime.serve import (make_paged_serve_step, make_prefill_step,
                                 make_serve_step)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--path", choices=["shortcut", "paged"],
                    default="shortcut")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    B, S = args.batch, args.prompt_len
    s_cap = S + args.gen + 8
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed),
                           jnp.float32)
    key = jax.random.PRNGKey(args.seed + 1)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.input_mode == "embeddings":
        batch = {"embeddings": params["embed"][toks]}
    elif cfg.input_mode == "prefix_embeddings":
        batch["prefix_embeddings"] = jax.random.normal(
            key, (B, cfg.prefix_len, cfg.d_model), jnp.float32) * 0.02

    t0 = time.perf_counter()
    if args.path == "shortcut" or not cfg.has_attention:
        prefill = make_prefill_step(cfg, s_cap=s_cap, dtype=jnp.float32)
        serve = jax.jit(make_serve_step(cfg))
        logits, state = prefill(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs = [tok]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            tok, state = serve(params, state, tok)
            outs.append(tok)
        jax.block_until_ready(tok)
    else:
        bs = 8
        cache = pc.cache_create(
            cfg.num_layers, num_blocks=B * (s_cap // bs + 1),
            block_size=bs, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, max_seqs=B,
            max_blocks_per_seq=s_cap // bs + 1, dtype=jnp.float32)
        logits, caches = M.prefill_forward(params, cfg, batch)
        cache = pc.write_prefill(cache, jnp.arange(B), caches.k, caches.v)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        serve = jax.jit(make_paged_serve_step(cfg))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        seq_ids = jnp.arange(B, dtype=jnp.int32)
        outs = [tok]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            tok, cache = serve(params, cache, tok, seq_ids)
            outs.append(tok)
        jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.stack([np.asarray(t) for t in outs], axis=1)
    print(f"[serve/{args.path}] arch={cfg.name} B={B} prompt={S} "
          f"gen={args.gen}")
    print(f"  prefill: {t_prefill * 1e3:8.1f} ms "
          f"({B * S / t_prefill:9.0f} tok/s)")
    print(f"  decode:  {t_decode * 1e3:8.1f} ms "
          f"({B * (args.gen - 1) / max(t_decode, 1e-9):9.0f} tok/s)")
    print(f"  sample tokens[0]: {gen[0][:12].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
