"""Mamba2 block via SSD (state-space duality), per arXiv:2405.21060.

Prefill/train use the chunked dual form: intra-chunk attention-like
(C Bᵀ ⊙ L) matmuls + an inter-chunk state recurrence (lax.scan). Decode is
the pure recurrent step. The chunked intra-chunk matmuls are the compute
hot-spot and have a Pallas twin in ``repro.kernels.ssd_scan``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.runtime import RunConfig
from repro.models.layers import ParamSpec, rms_norm


def ssm_param_specs(cfg: ModelConfig, n_layers: int) -> dict:
    """Stacked (leading ``layers`` axis) Mamba2 params."""
    s = cfg.ssm
    d = cfg.d_model
    di = s.inner(d)
    nh = s.n_ssm_heads(d)
    conv_dim = di + 2 * s.d_state
    L = (n_layers,)
    lx = ("layers",)
    return {
        # z (gate), x, B, C, dt
        "in_proj": ParamSpec(
            L + (d, 2 * di + 2 * s.d_state + nh), lx + ("embed", "ssm_inner")
        ),
        "conv_w": ParamSpec(L + (s.d_conv, conv_dim), lx + (None, "ssm_inner")),
        "conv_b": ParamSpec(L + (conv_dim,), lx + ("ssm_inner",), init="zeros"),
        "dt_bias": ParamSpec(L + (nh,), lx + ("ssm_heads",), init="zeros"),
        "A_log": ParamSpec(L + (nh,), lx + ("ssm_heads",), init="ones"),
        "D": ParamSpec(L + (nh,), lx + ("ssm_heads",), init="ones"),
        "norm_w": ParamSpec(L + (di,), lx + ("ssm_inner",), init="ones"),
        "out_proj": ParamSpec(L + (di, d), lx + ("ssm_inner", "embed")),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    s = cfg.ssm
    di = s.inner(cfg.d_model)
    nh = s.n_ssm_heads(cfg.d_model)
    z, xbc_x, Bm, Cm, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + s.d_state, 2 * di + 2 * s.d_state], axis=-1
    )
    return z, xbc_x, Bm, Cm, dt, di, nh


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. xbc: (B,S,C), w: (K,C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(k)
    )
    return jax.nn.silu(out + b[None, None, :])


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x:  (B,S,nh,hd)   dt: (B,S,nh)   A: (nh,)  [negative]
    Bm: (B,S,N)       Cm: (B,S,N)    (ngroups=1)
    Returns y: (B,S,nh,hd) and final state (B,nh,hd,N).
    """
    b, s, nh, hd = x.shape
    n = Bm.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    xq = x.reshape(b, nc, chunk, nh, hd)
    dtq = dt.reshape(b, nc, chunk, nh)
    Bq = Bm.reshape(b, nc, chunk, n)
    Cq = Cm.reshape(b, nc, chunk, n)

    dA = dtq * A[None, None, None, :]  # (B,nc,Q,nh) <= 0
    dA_cs = jnp.cumsum(dA, axis=2)  # inclusive cumsum within chunk

    # --- intra-chunk (diagonal) blocks: Y_ij = C_i·B_j exp(cs_i - cs_j) dt_j x_j
    att = jnp.einsum("bcqn,bckn->bcqk", Cq.astype(jnp.float32), Bq.astype(jnp.float32))
    decay = jnp.exp(dA_cs[:, :, :, None, :] - dA_cs[:, :, None, :, :])  # (b,c,q,k,h)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(mask[None, None, :, :, None], decay, 0.0)
    xdt = xq * dtq[..., None]  # (b,c,q,h,p)
    y_diag = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp", att, L, xdt.astype(jnp.float32))

    # --- chunk end-states: S_c = sum_j exp(cs_last - cs_j) B_j ⊗ (dt_j x_j)
    seg = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (b,c,q,h)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bq.astype(jnp.float32), seg, xdt.astype(jnp.float32))

    # --- inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))  # (b,c,h)
    s0 = (
        jnp.zeros((b, nh, hd, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(carry, xs):
        st_in, dec = xs  # (b,h,p,n), (b,h)
        new = carry * dec[:, :, None, None] + st_in
        return new, carry  # emit state *entering* this chunk

    final, prev_states = jax.lax.scan(
        step,
        s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (b,c,h,p,n)

    # --- inter-chunk contribution: C_i · exp(cs_i) · S_prev
    instate_decay = jnp.exp(dA_cs)  # (b,c,q,h)
    y_off = jnp.einsum(
        "bcqn,bcqh,bchpn->bcqhp", Cq.astype(jnp.float32), instate_decay, prev_states
    )

    y = (y_diag + y_off).reshape(b, nc * chunk, nh, hd)[:, :s]
    return y.astype(x.dtype), final


def mamba2_forward(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B,S,d)
    rcfg: RunConfig,
    initial_state=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence Mamba2 block. Returns (out (B,S,d), final_state,
    conv_tail): the tail is the last d_conv - 1 conv inputs (zeros before
    the first position), the decode's conv state."""
    s = cfg.ssm
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xi, Bm, Cm, dt, di, nh = _split_proj(cfg, proj)
    xbc = jnp.concatenate([xi, Bm, Cm], axis=-1)
    k = s.d_conv - 1
    tail = jnp.pad(xbc, ((0, 0), (max(0, k - xbc.shape[1]), 0), (0, 0)))[:, -k:]
    xbc = _causal_conv(xbc, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype))
    xi, Bm, Cm = jnp.split(xbc, [di, di + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xi.reshape(*xi.shape[:2], nh, s.headdim)
    if rcfg.use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops

        y, state = ssd_ops.ssd(xh, dt, A, Bm, Cm, chunk=s.chunk_size,
                               initial_state=initial_state)
    else:
        y, state = ssd_chunked(xh, dt, A, Bm, Cm, s.chunk_size, initial_state)
    y = y + xh * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(*x.shape[:2], di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, state, tail


def mamba2_decode(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B,1,d)
    ssm_state: jax.Array,  # (B,nh,hd,N)
    conv_state: jax.Array,  # (B,d_conv-1,conv_dim)
):
    """Single recurrent step. Returns (out (B,1,d), new_ssm, new_conv)."""
    s = cfg.ssm
    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xi, Bm, Cm, dt, di, nh = _split_proj(cfg, proj)
    xbc = jnp.concatenate([xi, Bm, Cm], axis=-1)  # (B,1,conv_dim)
    window = jnp.concatenate([conv_state, xbc], axis=1)  # (B,d_conv,conv_dim)
    new_conv = window[:, 1:]
    w = p["conv_w"].astype(x.dtype)
    conv_out = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(x.dtype)
    xbc = jax.nn.silu(conv_out)[:, None, :]
    xi, Bm, Cm = jnp.split(xbc, [di, di + s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xi.reshape(x.shape[0], nh, s.headdim)  # (B,nh,hd)
    dt1 = dt[:, 0]  # (B,nh)
    dA = jnp.exp(dt1 * A[None, :])  # (B,nh)
    dBx = jnp.einsum("bn,bh,bhp->bhpn", Bm[:, 0].astype(jnp.float32), dt1,
                     xh.astype(jnp.float32))
    new_state = ssm_state * dA[:, :, None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), new_state)
    y = y.astype(x.dtype) + xh * p["D"].astype(x.dtype)[None, :, None]
    y = y.reshape(x.shape[0], 1, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, new_state.astype(ssm_state.dtype), new_conv
