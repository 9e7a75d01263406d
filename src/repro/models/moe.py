"""Mixture-of-Experts FFN.

Two implementations, and a third for a layer that holds a share:
  * ``dense``            — compute-all-experts + one-hot combine. Exact and
                           differentiable; used for reduced smoke configs
                           and single-device runs (its FLOP waste is E/k×).
  * ``expert_parallel``  — production path: tokens sharded over the data
                           axes, experts sharded over "model". Inside
                           shard_map: router → top-k → sort-by-expert →
                           fixed-capacity dispatch buffers → all_to_all →
                           grouped per-expert GEMMs → all_to_all back →
                           weighted combine → all_gather. Exactly two
                           all-to-alls per MoE layer, matching the
                           collective roofline of a real MoE pod.
  * held                 — a chip's share under expert parallelism, without
                           the exchange: routes over every expert, keeps the
                           (token, expert) pairs whose expert it holds and
                           runs each held expert over its own rows as a
                           grouped matrix product. No token is dropped.
                           Taken whenever the router scores more experts
                           than the layer holds, whatever ``moe_impl`` says.

Token dropping in ``expert_parallel`` follows the standard fixed-capacity model
(capacity = ceil(T_sub·k·cf / E)).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.configs.base import ModelConfig
from repro.configs.runtime import RunConfig
from repro.models.layers import ParamSpec, swiglu


def moe_param_specs(cfg: ModelConfig, n_layers: int) -> dict:
    e = cfg.moe
    d = cfg.d_model
    L = (n_layers,)
    lx = ("layers",)
    specs = {
        "router": ParamSpec(L + (d, e.router_width), lx + ("embed", None)),
        "we_gate": ParamSpec(
            L + (e.n_experts, d, e.d_ff_expert), lx + ("experts", "embed", "ff")
        ),
        "we_up": ParamSpec(
            L + (e.n_experts, d, e.d_ff_expert), lx + ("experts", "embed", "ff")
        ),
        "we_down": ParamSpec(
            L + (e.n_experts, e.d_ff_expert, d), lx + ("experts", "ff", "embed")
        ),
    }
    if e.n_shared_experts:
        ff_sh = e.shared_width
        specs.update(
            {
                "ws_gate": ParamSpec(L + (d, ff_sh), lx + ("embed", "ff")),
                "ws_up": ParamSpec(L + (d, ff_sh), lx + ("embed", "ff")),
                "ws_down": ParamSpec(L + (ff_sh, d), lx + ("ff", "embed")),
            }
        )
    return specs


def _route(cfg: ModelConfig, router_w: jax.Array, xt: jax.Array):
    """xt: (T,d) -> (weights (T,k), ids (T,k), probs (T,E))."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, e.top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi, probs


def _aux_loss(cfg: ModelConfig, probs: jax.Array, topi: jax.Array) -> jax.Array:
    """Switch-style load-balance loss: E · Σ_e f_e · P_e."""
    e = cfg.moe
    onehot = jax.nn.one_hot(topi, e.n_experts, dtype=jnp.float32)  # (T,k,E)
    f = onehot.sum((0, 1)) / (topi.shape[0] * e.top_k)
    p = probs.mean(0)
    return e.n_experts * jnp.sum(f * p)


def _shared(p: dict, xt: jax.Array) -> jax.Array:
    if "ws_gate" not in p:
        return jnp.zeros_like(xt)
    return swiglu(xt, p["ws_gate"], p["ws_up"], p["ws_down"])


def moe_ffn_dense(cfg: ModelConfig, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Compute-all-experts reference path. x: (B,S,d)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    topw, topi, probs = _route(cfg, p["router"], xt)
    cdt = x.dtype
    g = jnp.einsum("td,edf->tef", xt, p["we_gate"].astype(cdt))
    u = jnp.einsum("td,edf->tef", xt, p["we_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("tef,efd->ted", h, p["we_down"].astype(cdt))
    combine = jnp.zeros((xt.shape[0], cfg.moe.n_experts), jnp.float32)
    combine = jax.vmap(lambda c, i, w: c.at[i].add(w))(combine, topi, topw)
    y = jnp.einsum("ted,te->td", ye.astype(jnp.float32), combine).astype(cdt)
    y = y + _shared(p, xt)
    return y.reshape(b, s, d), _aux_loss(cfg, probs, topi)


# ---------------------------------------------------------------------------
# Expert-parallel path
# ---------------------------------------------------------------------------


def _capacity(cfg: ModelConfig, t_sub: int, cf: float) -> int:
    e = cfg.moe
    cap = int(math.ceil(t_sub * e.top_k * cf / e.n_experts))
    return max(8, -(-cap // 8) * 8)  # round up to a multiple of 8


def moe_ffn_ep(
    cfg: ModelConfig,
    rcfg: RunConfig,
    mesh,
    p: dict,
    x: jax.Array,  # (B,S,d) global
) -> Tuple[jax.Array, jax.Array]:
    e = cfg.moe
    model_axis = "model"
    n_model = mesh.shape[model_axis]
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    b, s, d = x.shape
    batch_spec = data_axes if (b % max(n_data, 1) == 0 and n_data > 1) else None
    x_spec = P(batch_spec, None, None)

    def block(xl, router_w, wg, wu, wd, shared_p):
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        # each model shard routes its own slice of the local tokens
        t_pad = -(-t // n_model) * n_model
        xt_p = jnp.pad(xt, ((0, t_pad - t), (0, 0)))
        t_sub = t_pad // n_model
        midx = jax.lax.axis_index(model_axis)
        xs = jax.lax.dynamic_slice_in_dim(xt_p, midx * t_sub, t_sub)  # (Tsub,d)

        topw, topi, probs = _route(cfg, router_w, xs)
        tk = t_sub * e.top_k
        eid = topi.reshape(tk)
        tokid = jnp.repeat(jnp.arange(t_sub), e.top_k)
        w_assign = topw.reshape(tk)

        cap = _capacity(cfg, t_sub, rcfg.capacity_factor)
        order = jnp.argsort(eid)  # stable
        eid_s, tok_s, w_s = eid[order], tokid[order], w_assign[order]
        counts = jnp.zeros((e.n_experts,), jnp.int32).at[eid].add(1)
        start = jnp.cumsum(counts) - counts
        pos = jnp.arange(tk) - start[eid_s]
        pos = jnp.where(pos < cap, pos, cap)  # cap -> out of bounds -> dropped

        buf = jnp.zeros((e.n_experts, cap, d), xt.dtype)
        buf = buf.at[eid_s, pos].set(xs[tok_s], mode="drop")
        # -> expert owners: (E_loc, n_model*cap, d)
        recv = jax.lax.all_to_all(
            buf, model_axis, split_axis=0, concat_axis=1, tiled=True
        )
        cdt = xt.dtype
        g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(cdt))
        u = jnp.einsum("ecd,edf->ecf", recv, wu.astype(cdt))
        yexp = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(cdt))
        back = jax.lax.all_to_all(
            yexp, model_axis, split_axis=1, concat_axis=0, tiled=True
        )  # (E, cap, d) — original dispatch layout
        y_tok = back.at[eid_s, jnp.minimum(pos, cap - 1)].get(mode="clip")
        y_tok = jnp.where((pos < cap)[:, None], y_tok, 0.0)
        contrib = y_tok * w_s[:, None].astype(y_tok.dtype)
        ysub = jnp.zeros((t_sub, d), jnp.float32).at[tok_s].add(
            contrib.astype(jnp.float32)
        )
        ysub = ysub.astype(cdt) + _shared(shared_p, xs)
        yl = jax.lax.all_gather(ysub, model_axis, axis=0, tiled=True)  # (t_pad,d)
        yl = yl[:t].reshape(bl, sl, d)
        aux = _aux_loss(cfg, probs, topi)
        aux = jax.lax.pmean(aux, model_axis)
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
        return yl, aux

    shared_p = {k: p[k] for k in ("ws_gate", "ws_up", "ws_down") if k in p}
    shared_specs = {k: P(None, None) for k in shared_p}
    out = shard_map(
        block,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
            shared_specs,
        ),
        out_specs=(x_spec, P()),
        check_rep=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared_p)
    return out


def moe_ffn_ep2d(
    cfg: ModelConfig,
    rcfg: RunConfig,
    mesh,
    p: dict,
    x: jax.Array,  # (B,S,d) global
) -> Tuple[jax.Array, jax.Array]:
    """2D expert sharding for serving: experts→model, d_ff_expert→data.

    Expert weights stay fully sharded across all 256 chips (they must —
    236B does not fit replicated), but instead of fsdp-gathering ~50 GB of
    weights per decode step, each data shard computes a d_ff slice of every
    expert and the down-projection partial-sums reduce with a ~MB-scale
    activation psum. Token counts at decode are tiny, so replicating them
    over the data axes is free.
    """
    e = cfg.moe
    model_axis = "model"
    n_model = mesh.shape[model_axis]
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    b, s, d = x.shape

    def block(xl, router_w, wg, wu, wd, shared_p):
        # xl: full (B,S,d); wg/wu: (E_loc, d, ff_loc); wd: (E_loc, ff_loc, d)
        t = b * s
        xt = xl.reshape(t, d)
        t_pad = -(-t // n_model) * n_model
        xt_p = jnp.pad(xt, ((0, t_pad - t), (0, 0)))
        t_sub = t_pad // n_model
        midx = jax.lax.axis_index(model_axis)
        xs = jax.lax.dynamic_slice_in_dim(xt_p, midx * t_sub, t_sub)

        topw, topi, probs = _route(cfg, router_w, xs)
        tk = t_sub * e.top_k
        eid = topi.reshape(tk)
        tokid = jnp.repeat(jnp.arange(t_sub), e.top_k)
        w_assign = topw.reshape(tk)
        cap = _capacity(cfg, t_sub, rcfg.capacity_factor)
        order = jnp.argsort(eid)
        eid_s, tok_s, w_s = eid[order], tokid[order], w_assign[order]
        counts = jnp.zeros((e.n_experts,), jnp.int32).at[eid].add(1)
        start = jnp.cumsum(counts) - counts
        pos = jnp.arange(tk) - start[eid_s]
        pos = jnp.where(pos < cap, pos, cap)

        buf = jnp.zeros((e.n_experts, cap, d), xt.dtype)
        buf = buf.at[eid_s, pos].set(xs[tok_s], mode="drop")
        recv = jax.lax.all_to_all(
            buf, model_axis, split_axis=0, concat_axis=1, tiled=True
        )
        cdt = xt.dtype
        g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(cdt))
        u = jnp.einsum("ecd,edf->ecf", recv, wu.astype(cdt))
        yexp = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(cdt))
        # partial over the ff slice -> reduce across the data axes
        for ax in data_axes:
            yexp = jax.lax.psum(yexp, ax)
        back = jax.lax.all_to_all(
            yexp, model_axis, split_axis=1, concat_axis=0, tiled=True
        )
        y_tok = back.at[eid_s, jnp.minimum(pos, cap - 1)].get(mode="clip")
        y_tok = jnp.where((pos < cap)[:, None], y_tok, 0.0)
        contrib = y_tok * w_s[:, None].astype(y_tok.dtype)
        ysub = jnp.zeros((t_sub, d), jnp.float32).at[tok_s].add(
            contrib.astype(jnp.float32)
        ).astype(cdt)
        if "ws_gate" in shared_p:  # shared experts: same ff-slice + psum
            gs = jnp.einsum("td,df->tf", xs, shared_p["ws_gate"].astype(cdt))
            us = jnp.einsum("td,df->tf", xs, shared_p["ws_up"].astype(cdt))
            ys = jnp.einsum("tf,fd->td", jax.nn.silu(gs) * us,
                            shared_p["ws_down"].astype(cdt))
            for ax in data_axes:
                ys = jax.lax.psum(ys, ax)
            ysub = ysub + ys
        yl = jax.lax.all_gather(ysub, model_axis, axis=0, tiled=True)
        yl = yl[:t].reshape(b, s, d)
        aux = _aux_loss(cfg, probs, topi)
        aux = jax.lax.pmean(aux, model_axis)
        return yl, aux

    shared_p = {k: p[k] for k in ("ws_gate", "ws_up", "ws_down") if k in p}
    da = data_axes[0] if len(data_axes) == 1 else data_axes
    shared_specs = {
        "ws_gate": P(None, da),
        "ws_up": P(None, da),
        "ws_down": P(da, None),
    }
    shared_specs = {k: v for k, v in shared_specs.items() if k in shared_p}
    out = shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(None, None, None),  # tokens replicated (tiny at decode)
            P(None, None),
            P(model_axis, None, da),
            P(model_axis, None, da),
            P(model_axis, da, None),
            shared_specs,
        ),
        out_specs=(P(None, None, None), P()),
        check_rep=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared_p)
    return out


# ---------------------------------------------------------------------------
# Held-expert path (a chip's share of an expert-parallel layer)
# ---------------------------------------------------------------------------

HELD_BLOCK = 2048  # tokens routed together: bounds the gathered rows


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 that divides ``size``, at most ``most``."""
    return max(t for t in range(128, min(size, most) + 1, 128) if size % t == 0)


def _grouped(rows, w, sizes, tm: int, pallas: bool):
    """rows (m, k) sorted by group, w (G, k, n), sizes (G,): each group's
    rows times its matrix. Rows past sum(sizes) are left undefined. The
    kernel's weight tiles are large (up to 2048 × 768 at tm = 128), so a
    decode's few rows per expert stream each expert's matrix in a few
    steps."""
    if pallas:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        from repro.kernels.runtime import default_interpret

        most = 2048 if tm == 128 else 1024  # the tiles fit the scoped VMEM
        k, n = w.shape[1:]
        return gmm(rows, w.astype(rows.dtype), sizes,
                   preferred_element_type=rows.dtype,
                   tiling=(tm, _tile(k, most), _tile(n, most)),
                   interpret=default_interpret())
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)


def _held_block(cfg: ModelConfig, p: dict, xt: jax.Array, pallas: bool) -> jax.Array:
    """The held experts' gated sum for the tokens xt (T, d), float32."""
    e = cfg.moe
    t, d = xt.shape
    held = e.n_experts
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    top, ids = jax.lax.top_k(logits, e.top_k)
    gates = jax.nn.softmax(top, axis=-1)  # over the chosen experts only
    local = (ids - e.first_held).reshape(-1)
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)  # held pairs by expert, the rest last
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    # a token holds at most min(k, held) of the pairs kept: every kept pair
    # has a row; the rows fill whole tiles of the grouped kernel
    tm = 512 if t >= 512 else 128
    m = -(-t * min(e.top_k, held) // tm) * tm
    order = jnp.pad(order, (0, max(0, m - order.size)))[:m]
    token = order // e.top_k
    valid = jnp.arange(m) < sizes.sum()
    rows = xt[token]
    g = _grouped(rows, p["we_gate"], sizes, tm, pallas)
    u = _grouped(rows, p["we_up"], sizes, tm, pallas)
    y = _grouped(jax.nn.silu(g) * u, p["we_down"], sizes, tm, pallas)
    w = jnp.where(valid, gates.reshape(-1)[order], 0.0)
    y = jnp.where(valid[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
    return jnp.zeros((t, d), jnp.float32).at[token].add(y)


def moe_ffn_held(
    cfg: ModelConfig, rcfg: RunConfig, p: dict, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer plus the shared expert, for
    x (B, S, d); tokens go through the router in blocks of ``HELD_BLOCK``.
    With ``use_pallas`` the grouped products run in the megablox kernel
    (interpreted off a TPU), else in ``lax.ragged_dot``."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    t = b * s
    if t <= HELD_BLOCK:
        y = _held_block(cfg, p, xt, rcfg.use_pallas)
    else:
        nb = -(-t // HELD_BLOCK)
        xb = jnp.pad(xt, ((0, nb * HELD_BLOCK - t), (0, 0)))
        y = jax.lax.map(lambda blk: _held_block(cfg, p, blk, rcfg.use_pallas),
                        xb.reshape(nb, HELD_BLOCK, d))
        y = y.reshape(nb * HELD_BLOCK, d)[:t]
    y = y.astype(x.dtype) + _shared(p, xt)
    return y.reshape(b, s, d), jnp.zeros((), jnp.float32)


def moe_ffn(
    cfg: ModelConfig,
    rcfg: RunConfig,
    mesh,
    p: dict,
    x: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe.router_width != cfg.moe.n_experts:
        return moe_ffn_held(cfg, rcfg, p, x)
    impl = rcfg.moe_impl
    if impl == "auto":
        impl = (
            "expert_parallel"
            if mesh is not None
            and mesh.shape.get("model", 1) > 1
            and cfg.moe.n_experts % mesh.shape["model"] == 0
            else "dense"
        )
    if impl == "expert_parallel_2d":
        return moe_ffn_ep2d(cfg, rcfg, mesh, p, x)
    if impl == "expert_parallel":
        return moe_ffn_ep(cfg, rcfg, mesh, p, x)
    return moe_ffn_dense(cfg, p, x)
