"""Jit'd public wrapper matching the model-side call convention
(B, S, H, D) ⇄ the kernel's (B, H, S, D). ``interpret=None`` routes
through ``repro.kernels.runtime.default_interpret`` inside the kernel."""
from __future__ import annotations

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


def flash_attention(
    qg: jax.Array,  # (B, Sq, Hkv, G, D) — model-side grouped layout
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    q_pos=None,
    kv_pos=None,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
    scale: float | None = None,
) -> jax.Array:
    b, sq, hkv, g, d = qg.shape
    q = qg.reshape(b, sq, hkv * g, d).transpose(0, 2, 1, 3)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    o = flash_attention_bhsd(
        q, kk, vv, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret, scale=scale,
    )
    return o.transpose(0, 2, 1, 3).reshape(b, sq, hkv, g, v.shape[-1])
