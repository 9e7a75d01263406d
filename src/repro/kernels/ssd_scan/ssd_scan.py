"""Mamba2 SSD chunked-scan Pallas TPU kernel (arXiv:2405.21060 Alg. SSD).

Grid (B, NH, NC) with the chunk axis iterated sequentially (minor), so the
(hd × N) recurrent state lives in VMEM scratch and carries across chunks —
the inter-chunk recurrence costs no HBM round-trips. Per chunk, the
intra-chunk dual form is three MXU matmuls:

  att    = C · Bᵀ                        (Q × Q)
  y_diag = (att ⊙ L) · (dt ⊙ x)          (Q × hd)
  y_off  = exp(cs) ⊙ (C · Sᵀ)            (Q × hd)  — S is the carried state
  S'     = exp(Σ dA)·S + (dt⊙x)ᵀ·(seg⊙B) (hd × N)

TPU adaptation: chunk Q and headdim/state sizes are chosen MXU-friendly
(multiples of 128 at deployment; tests sweep smaller interpret shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import default_interpret


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, sfin_ref, state_ref,
    *, nc, q, hd, n,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, hd)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    A = a_ref[0, pl.program_id(1)]  # this head's scalar (negative), SMEM
    Bm = b_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)

    dA = dt * A  # (Q, 1)
    xdt = x * dt  # (Q, hd)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # inclusive cumsum of dA as masked reductions (Mosaic lowers no
    # cumsum): the row form sums down the columns, the column form reads
    # the row form's diagonal back across the lanes
    cs_row = jnp.sum(jnp.where(ii <= jj, dA, 0.0), axis=0, keepdims=True)
    cs = jnp.sum(jnp.where(ii == jj, cs_row, 0.0), axis=1, keepdims=True)
    total = jnp.sum(dA, axis=0, keepdims=True)  # (1, 1) = cs[-1]

    # intra-chunk
    att = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    L = jnp.where(ii >= jj, jnp.exp(cs - cs_row), 0.0)
    y = jax.lax.dot_general(
        att * L, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, hd)

    # inter-chunk contribution from the carried state
    state = state_ref[...]  # (hd, N)
    y_off = jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, hd)
    y = y + jnp.exp(cs) * y_off
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update
    seg = jnp.exp(total - cs)  # (Q, 1)
    new_state = state * jnp.exp(total) + jax.lax.dot_general(
        xdt, Bm * seg, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (hd, N)
    state_ref[...] = new_state

    @pl.when(ci == nc - 1)
    def _fin():
        sfin_ref[0, 0, 0] = new_state.astype(sfin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,  # (B, S, NH, hd)
    dt: jax.Array,  # (B, S, NH) — post-softplus
    A: jax.Array,  # (NH,) negative
    Bm: jax.Array,  # (B, S, N)  (ngroups=1)
    Cm: jax.Array,  # (B, S, N)
    chunk: int = 256,
    initial_state=None,
    interpret: bool | None = None,
):
    """Returns (y (B,S,NH,hd), final_state (B,NH,hd,N))."""
    if interpret is None:  # static param: resolved at trace time
        interpret = default_interpret()
    b, s, nh, hd = x.shape
    n = Bm.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    # layout: (B, NH, NC, Q, ·)
    xq = x.transpose(0, 2, 1, 3).reshape(b, nh, nc, chunk, hd)
    dtq = dt.transpose(0, 2, 1).reshape(b, nh, nc, chunk, 1)
    bq = Bm.reshape(b, 1, nc, chunk, n)
    cq = Cm.reshape(b, 1, nc, chunk, n)
    a2 = A.reshape(1, nh).astype(jnp.float32)
    s0 = (
        jnp.zeros((b, nh, hd, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    s0 = s0.reshape(b, nh, 1, hd, n)

    y, sfin = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, q=chunk, hd=hd, n=n),
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, hd), lambda b_, h, c: (b_, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda b_, h, c: (b_, h, c, 0, 0)),
            # the whole (1, NH) row of A in SMEM: a (1, 1) VMEM block of
            # it breaks Mosaic's (8, 128) tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, chunk, n), lambda b_, h, c: (b_, 0, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, n), lambda b_, h, c: (b_, 0, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, hd, n), lambda b_, h, c: (b_, h, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, hd), lambda b_, h, c: (b_, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, hd, n), lambda b_, h, c: (b_, h, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, nc, chunk, hd), x.dtype),
            jax.ShapeDtypeStruct((b, nh, 1, hd, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, n), jnp.float32)],
        interpret=interpret,
    )(xq, dtq, a2, bq, cq, s0)

    y = y.reshape(b, nh, sp, hd).transpose(0, 2, 1, 3)[:, :s]
    return y, sfin.reshape(b, nh, hd, n)
