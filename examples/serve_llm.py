"""Serve a (reduced) model with batched requests: prefill + decode loop.

    PYTHONPATH=src python examples/serve_llm.py [--arch qwen2-1.5b]

Demonstrates the serving substrate: KV-cache init, batched prefill,
greedy decode steps — the same ``serve_step`` the decode_32k / long_500k
dry-run cells lower on the production mesh, plus the int8 weight-only
quantization path from the §Perf hillclimb.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import get_config
from repro.data.specs import reduced_config
from repro.launch.compile_cache import configure_compile_cache
from repro.serving.engine import greedy_sample, make_serve_step
from repro.serving.quant import dequantize_params, quantize_params


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--int8", action="store_true")
    args = ap.parse_args()

    cfg = reduced_config(get_config(args.arch))
    assert cfg.family in ("dense", "moe", "ssm", "hybrid"), \
        "token-in archs only for this demo"
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    if args.int8:
        desc = models.param_desc(cfg)
        qp = quantize_params(params, desc)
        params = dequantize_params(qp, jnp.dtype(cfg.dtype))
        print("[serve] int8 weight-only quantization applied")

    rng = np.random.default_rng(0)
    b = args.batch
    max_len = args.prompt_len + args.new_tokens
    prompts = rng.integers(0, cfg.vocab_size, (b, args.prompt_len))

    cache = models.init_cache(cfg, b, max_len)
    serve = jax.jit(make_serve_step(cfg))

    # prefill via sequential decode (robust across all families)
    t0 = time.perf_counter()
    for t in range(args.prompt_len):
        batch = {"tokens": jnp.asarray(prompts[:, t:t + 1], jnp.int32),
                 "positions": jnp.full((b, 1), t, jnp.int32)}
        logits, cache = serve(params, cache, batch)
    print(f"[serve] prefill {args.prompt_len} tokens x{b} in "
          f"{time.perf_counter() - t0:.2f}s")

    tok = greedy_sample(logits)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(args.prompt_len, max_len - 1):
        batch = {"tokens": tok[:, None],
                 "positions": jnp.full((b, 1), t, jnp.int32)}
        logits, cache = serve(params, cache, batch)
        tok = greedy_sample(logits)
        out.append(tok)
    dt = time.perf_counter() - t0
    gen = np.stack([np.asarray(t) for t in out], axis=1)
    print(f"[serve] generated {gen.shape[1]} tokens x{b} at "
          f"{gen.shape[1] * b / dt:.1f} tok/s (batched)")
    print("[serve] sample token ids:", gen[0][:12].tolist())
    print("OK")


if __name__ == "__main__":
    main()
