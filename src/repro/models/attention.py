"""Attention: GQA/MHA with causal + sliding-window masking.

Two execution paths:
  * direct   — single einsum; used for short KV (decode steps, smoke tests).
  * blocked  — online-softmax over (block_q × block_k) tiles via lax.map /
               lax.scan; used for long-sequence prefill/training so the
               S×S score matrix never materializes. This is the XLA twin of
               ``repro.kernels.flash_attention`` (the TPU Pallas deployment
               path) and what the multi-pod dry-run lowers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.runtime import RunConfig

NEG_INF = -1e30


def _mask(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """q_pos: (..., Sq), kv_pos: (..., Skv) -> bool (..., Sq, Skv)."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    m = kp >= 0  # negative kv positions mark invalid (unwritten ring slots)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= (qp - kp) < window
    return m


def _direct(q, k, v, q_pos, kv_pos, causal, window, scale):
    b, sq, hkv, g, d = q.shape
    # bf16 operands + f32 accumulation: avoids materializing an f32 copy of
    # the KV cache on the decode path (§Perf hillclimb #3)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    m = _mask(q_pos, kv_pos, causal, window)  # (B,Sq,Skv)
    scores = jnp.where(m[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out


def _blocked(q, k, v, q_pos, kv_pos, causal, window, scale, bq, bk):
    """Online-softmax attention over q/kv tiles (flash-style, pure XLA)."""
    b, sq, hkv, g, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    # pad seq lens to multiples of the block sizes
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=-1)
    nq, nk = (sq + pq) // bq, (skv + pk) // bk

    qb = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(b, nq, bq).transpose(1, 0, 2)
    kb = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, bk, hkv, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    kpb = kv_pos.reshape(b, nk, bk).transpose(1, 0, 2)

    def per_qblock(args):
        qi, qpi = args  # (B,bq,Hkv,G,D), (B,bq)

        def kv_step(carry, xs):
            acc, mx, dn = carry
            ki, vi, kpi = xs  # (B,bk,Hkv,D), (B,bk)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), ki.astype(jnp.float32)
            ) * scale
            m = _mask(qpi, kpi, causal, window)
            s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
            mx_new = jnp.maximum(mx, s.max(axis=-1))
            corr = jnp.exp(mx - mx_new)
            p = jnp.exp(s - mx_new[..., None])
            dn = dn * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (acc, mx_new, dn), None

        acc0 = jnp.zeros((b, hkv, g, bq, v.shape[-1]), jnp.float32)
        mx0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        dn0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        (acc, _, dn), _ = jax.lax.scan(kv_step, (acc0, mx0, dn0), (kb, vb, kpb))
        out = acc / jnp.maximum(dn[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)  # (B,bq,Hkv,G,D)

    ob = jax.lax.map(per_qblock, (qb, qpb))  # (nq,B,bq,Hkv,G,Dv)
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pq, hkv, g, v.shape[-1])
    return out[:, :sq].astype(v.dtype)


def _blocked_swa(q, k, v, q_pos, kv_pos, window, scale, bq, bk):
    """Sliding-window attention with static KV slicing.

    For a static window W and contiguous positions (training/prefill), each
    q tile only attends to the ⌈(W+bq)/bk⌉+1 KV tiles covering
    [q_start − W, q_end] — O(S·W) work instead of a masked O(S²) grid.
    """
    b, sq, hkv, g, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    pq, pk = (-sq) % bq, (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=-1)
    nq, nk = (sq + pq) // bq, (skv + pk) // bk
    nspan = min((window + bq) // bk + 2, nk)

    qb = q.reshape(b, nq, bq, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    qpb = q_pos.reshape(b, nq, bq).transpose(1, 0, 2)
    kb = k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, bk, hkv, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    kpb = kv_pos.reshape(b, nk, bk).transpose(1, 0, 2)

    def per_qblock(args):
        i, qi, qpi = args  # block idx, (B,bq,Hkv,G,D), (B,bq)
        start = jnp.clip((i * bq - window) // bk, 0, nk - nspan)
        kspan = jax.lax.dynamic_slice_in_dim(kb, start, nspan, axis=0)
        vspan = jax.lax.dynamic_slice_in_dim(vb, start, nspan, axis=0)
        pspan = jax.lax.dynamic_slice_in_dim(kpb, start, nspan, axis=0)

        def kv_step(carry, xs):
            acc, mx, dn = carry
            ki, vi, kpi = xs
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), ki.astype(jnp.float32)
            ) * scale
            m = _mask(qpi, kpi, True, window)
            s = jnp.where(m[:, None, None, :, :], s, NEG_INF)
            mx_new = jnp.maximum(mx, s.max(axis=-1))
            corr = jnp.exp(mx - mx_new)
            p = jnp.exp(s - mx_new[..., None])
            dn = dn * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (acc, mx_new, dn), None

        acc0 = jnp.zeros((b, hkv, g, bq, v.shape[-1]), jnp.float32)
        mx0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        dn0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        (acc, _, dn), _ = jax.lax.scan(kv_step, (acc0, mx0, dn0),
                                       (kspan, vspan, pspan))
        out = acc / jnp.maximum(dn[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)

    ob = jax.lax.map(per_qblock, (jnp.arange(nq), qb, qpb))
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq + pq, hkv, g, v.shape[-1])
    return out[:, :sq].astype(v.dtype)


def attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
    q_pos: jax.Array,  # (B, Sq) int32
    kv_pos: jax.Array,  # (B, Skv) int32 (negative = invalid slot)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    rcfg: RunConfig = RunConfig(),
    scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention. Returns (B, Sq, Hq, D). ``scale``: the
    softmax scale, 1/sqrt(D) when None."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    if rcfg.use_pallas and sq > 1:
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(
            qg, k, v, q_pos, kv_pos, causal=causal, window=window,
            block_q=rcfg.attn_block_q, block_k=rcfg.attn_block_k, scale=scale,
        )
        return out.reshape(b, sq, hq, v.shape[-1]).astype(v.dtype)
    scale = (
        1.0 / jnp.sqrt(d).astype(jnp.float32) if scale is None
        else jnp.float32(scale)
    )
    if sq * k.shape[1] > rcfg.attn_blocked_threshold**2:
        if (
            causal
            and isinstance(window, int)
            and sq == k.shape[1]
            and window < k.shape[1]
        ):
            out = _blocked_swa(
                qg, k, v, q_pos, kv_pos, window, scale,
                rcfg.attn_block_q, rcfg.attn_block_k,
            )
        else:
            out = _blocked(
                qg, k, v, q_pos, kv_pos, causal, window, scale,
                rcfg.attn_block_q, rcfg.attn_block_k,
            )
    else:
        out = _direct(qg, k, v, q_pos, kv_pos, causal, window, scale)
    return out.reshape(b, sq, hq, v.shape[-1]).astype(v.dtype)
