"""Batched serving example: prefill + decode loop with a KV cache.

    PYTHONPATH=src python examples/serve.py [--arch qwen3-4b] [--batch 4]

Serves a smoke-scale model: batches of prompts are prefilled, then decoded
token by token (greedy).  The same prefill/decode step functions lower to
the production pod meshes in repro.launch.dryrun.
"""
import argparse, sys, time
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import model_fns


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    cfg = ARCHS[args.arch].smoke()
    fns = model_fns(cfg)
    params = fns.init_params(jax.random.PRNGKey(0), cfg)
    B, P, G = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.padded_vocab, dtype=jnp.int32)

    if fns.is_encdec:
        frames = jax.random.normal(
            jax.random.PRNGKey(2), (B, 8, cfg.d_model)).astype(jnp.bfloat16)
        cache = fns.init_cache(cfg, B, P + G, 8)
        prefill = jax.jit(lambda p, f, t, c: fns.prefill(p, f, t, c, cfg))
        logits, cache = prefill(params, frames, prompts, cache)
    else:
        cache = fns.init_cache(cfg, B, P + G)
        prefill = jax.jit(lambda p, t, c: fns.prefill(p, t, c, cfg))
        logits, cache = prefill(params, prompts, cache)
    decode = jax.jit(lambda p, t, c: fns.decode_step(p, t, c, cfg))

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.monotonic()
    for _ in range(G - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.monotonic() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={B} prompt={P} generated={gen.shape[1]}")
    print(f"decode throughput: {B*(G-1)/dt:.1f} tok/s (CPU, smoke scale)")
    print("first sequence:", gen[0].tolist())


if __name__ == "__main__":
    main()
