"""Serving launcher: batched generation with the runtime's concurrency
knob. Reduced configs for the CPU tests; ``--full`` builds the published
widths and depth (use ``--param-dtype bfloat16`` on a 16 GB chip).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --requests 8 --prompt-len 32 --new-tokens 16 --concurrency 2
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.configs.runtime import serving_config
from repro.models.transformer import ApplyCtx, init_model_params
from repro.serving import Request, ServingEngine, ServingRuntime


def build_engine(
    arch: str,
    batch: int,
    max_len: int,
    reduced: bool = True,
    param_dtype: str = "float32",
    seed: int = 0,
) -> ServingEngine:
    """A serving engine over seeded weights: ``reduced`` picks the tiny
    same-family config, otherwise the published one."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rcfg = serving_config(param_dtype=param_dtype)
    ctx = ApplyCtx(cfg, rcfg, None)
    params = init_model_params(jax.random.PRNGKey(seed), cfg, rcfg)
    return ServingEngine(ctx, params, batch, max_len)


def serve(
    arch: str,
    requests: int = 8,
    prompt_len: int = 32,
    new_tokens: int = 16,
    batch: int = 4,
    concurrency: int = 1,
    seed: int = 0,
    reduced: bool = True,
    param_dtype: str = "float32",
):
    """Serve ``requests`` random prompts to completion; returns the drain
    metrics and the runtime (its engine and finished requests)."""
    engine = build_engine(
        arch, batch, prompt_len + new_tokens + 1, reduced, param_dtype, seed
    )
    runtime = ServingRuntime(engine, batch_size=batch, concurrency=concurrency)
    rng = np.random.default_rng(seed)
    vocab = engine.ctx.cfg.vocab
    for rid in range(requests):
        runtime.submit(
            Request(rid, rng.integers(0, vocab, prompt_len, dtype=np.int32),
                    new_tokens)
        )
    metrics = runtime.drain()
    print(
        f"{arch}: {metrics['requests']} requests, "
        f"{metrics['throughput_tok_s']:.1f} tok/s, "
        f"p50={metrics['p50_latency_s']*1e3:.0f}ms p99={metrics['p99_latency_s']*1e3:.0f}ms"
    )
    return metrics, runtime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--concurrency", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth instead of reduced()")
    ap.add_argument("--param-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    args = ap.parse_args()
    serve(args.arch, args.requests, args.prompt_len, args.new_tokens,
          args.batch, args.concurrency, reduced=not args.full,
          param_dtype=args.param_dtype)


if __name__ == "__main__":
    main()
