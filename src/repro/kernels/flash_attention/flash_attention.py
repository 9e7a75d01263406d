"""Flash attention Pallas TPU kernel: causal + sliding-window + GQA.

Online-softmax over (block_q × block_k) VMEM tiles; fp32 accumulators in
VMEM scratch; grid (B, Hq, nQ, nK) with the KV axis iterated sequentially
(minor) so running max / denominator / accumulator carry across k-steps.
GQA is expressed purely in the k/v BlockSpec index maps (head h reads KV
head h // group) — no KV head replication in HBM.

Assumes contiguous positions from 0 (training/prefill). Decode uses the
XLA path (Sq = 1 is bandwidth-bound; MXU tiling buys nothing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import default_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, bq, bk, nk, seq_k,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = kj * bk
    # cheap block-level skip: fully-masked tiles don't touch the MXU
    q_end = q_start + bq - 1
    relevant = True
    if causal:
        relevant = k_start <= q_end
    if window is not None:
        relevant = relevant & (k_start + bk - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention_bhsd(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, Dv)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
    scale: float | None = None,
) -> jax.Array:
    """``scale``: the softmax scale, 1/sqrt(D) when None."""
    if interpret is None:  # static param: resolved at trace time
        interpret = default_interpret()
    b, hq, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    assert hq % hkv == 0
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (sq + pq) // bq, (sk + pk) // bk
    if scale is None:
        scale = 1.0 / (d**0.5)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk, seq_k=sk,
        ),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pq, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
