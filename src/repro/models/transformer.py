"""Model assembly: every assigned architecture is built from one generic
decoder stack (+ optional encoder for enc-dec), driven entirely by
``ModelConfig``.

Layer stacking follows the MaxText pattern: per-layer parameters carry a
leading ``layers`` axis and the stack is applied with ``lax.scan`` (so a
94-layer config lowers/compiles one layer body). Heterogeneous leading
layers (dense-FFN prologue of DeepSeek/Moonlight MoE) are applied unrolled
before the scan. A config with per-layer kinds (``layer_types``: Mamba-2
and attention mixers in a published pattern, granite-4.0-h) stacks each
mixer over the layers of its kind and the norms and FFN over every
layer, and runs one scan per maximal run of one kind (``kind_runs``).

Three entry points per architecture:
  forward_train(ctx, params, batch)             -> (logits, aux)
  prefill(ctx, params, batch)                   -> (cache, last_logits)
  decode_step(ctx, params, cache, tokens)       -> (cache, logits)

``prefill`` and ``decode_step`` name their work with ``jax.named_scope``,
so a device trace can attribute each operation: ``embed``, ``attn`` (QKV,
rope, attention, output projection), ``kv_write`` (every cache write),
``mlp`` and ``head`` (final norm and unembed); in a stack with per-layer
kinds also ``ssm`` (the Mamba-2 mixer), ``ssm_write`` (its state and
conv writes) and ``moe`` (router, experts, shared expert, combine).

The cache stacks each leaf over its layers (with per-layer kinds, over
the layers of the leaf's kind). Attention K/V are stored
``(L, B, kv_heads, W, head_dim)``, the order decode attention reads, so a
layer's slab is a plain slice the attention fusion reads in place; MLA's
latent ``ckv``/``krope`` are ``(L, B, W, r)``. ``decode_step`` carries the
stacked leaves through the layer scan and updates them in place: the new
token is written at its ring slot, SSM/conv state is replaced, and each
layer reads its slab from the updated carry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.configs.runtime import RunConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import attention
from repro.models.layers import (
    ParamSpec,
    abstract_params,
    apply_mrope,
    apply_rope,
    init_params,
    layer_norm,
    param_axes,
    rms_norm,
    swiglu,
)
from repro.models.mla import mla_decode, mla_full, mla_param_specs

BIG_WINDOW = 1 << 30

# Ring-slot (sequence) axis of each stacked cache leaf that holds one entry
# per position: K/V (L, B, kv, W, hd); MLA's latent (L, B, W, r) has no
# head axis.
SLOT_AXIS = {"k": 3, "v": 3, "ckv": 2, "krope": 2}


def _to_cache(name: str, x: jax.Array) -> jax.Array:
    """A layer's (B, S, ...) entries of ring leaf ``name`` -> stored order."""
    return jnp.moveaxis(x, 1, SLOT_AXIS[name] - 1)


def _from_cache(name: str, x: jax.Array) -> jax.Array:
    """A layer's slab of ring leaf ``name`` -> (B, S, ...)."""
    return jnp.moveaxis(x, SLOT_AXIS[name] - 1, 1)


@dataclasses.dataclass(frozen=True)
class ApplyCtx:
    cfg: ModelConfig
    rcfg: RunConfig
    mesh: Any = None  # jax Mesh or None (single device)


def constrain_batch(ctx: ApplyCtx, x: jax.Array) -> jax.Array:
    """Pin activations to batch-sharding over the data axes.

    Without this, XLA's SPMD partitioner may resolve the fsdp weight
    sharding by replicating the token dimension instead of gathering the
    weights — flop-equivalent per chip for plain matmuls but catastrophic
    for attention (S² work replicated 16×) and activation memory.
    """
    if ctx.mesh is None:
        return x
    if getattr(ctx.rcfg, "decode_tp_over_data", False) and x.shape[1] == 1:
        return x  # decode TP mode: leave single-token activations unpinned
    import math

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.specs import data_axes

    da = data_axes(ctx.mesh)
    if not da:
        return x
    size = math.prod(ctx.mesh.shape[a] for a in da)
    if size <= 1 or x.shape[0] % size != 0:
        return x
    spec = P(da, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def _attn_specs(cfg: ModelConfig, n: int, cross: bool = False) -> dict:
    if cfg.mla is not None and not cross:
        return mla_param_specs(cfg, n)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    L = (n,)
    lx = ("layers",)
    s = {
        "wq": ParamSpec(L + (d, hq * hd), lx + ("embed", "heads_flat")),
        "wk": ParamSpec(L + (d, hkv * hd), lx + ("embed", "kv_heads_flat")),
        "wv": ParamSpec(L + (d, hkv * hd), lx + ("embed", "kv_heads_flat")),
        "wo": ParamSpec(L + (hq * hd, d), lx + ("heads_flat", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec(L + (hq * hd,), lx + ("heads_flat",), init="zeros")
        s["bk"] = ParamSpec(L + (hkv * hd,), lx + ("kv_heads_flat",), init="zeros")
        s["bv"] = ParamSpec(L + (hkv * hd,), lx + ("kv_heads_flat",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec(L + (hd,), lx + (None,), init="ones")
        s["k_norm"] = ParamSpec(L + (hd,), lx + (None,), init="ones")
    return s


def _ffn_specs(cfg: ModelConfig, n: int, dense: bool) -> dict:
    d = cfg.d_model
    L = (n,)
    lx = ("layers",)
    if dense or cfg.moe is None:
        s = {
            "wg": ParamSpec(L + (d, cfg.d_ff), lx + ("embed", "ff")),
            "wu": ParamSpec(L + (d, cfg.d_ff), lx + ("embed", "ff")),
            "wd": ParamSpec(L + (cfg.d_ff, d), lx + ("ff", "embed")),
        }
        if cfg.arch_type == "audio":  # whisper MLP: gelu with biases, no gate
            del s["wu"]
            s["bg"] = ParamSpec(L + (cfg.d_ff,), lx + ("ff",), init="zeros")
            s["bd"] = ParamSpec(L + (d,), lx + (None,), init="zeros")
        return s
    return moe_lib.moe_param_specs(cfg, n)


def _norm_specs(cfg: ModelConfig, n: int, name: str) -> dict:
    L = (n,)
    lx = ("layers",)
    s = {name: ParamSpec(L + (cfg.d_model,), lx + (None,), init="ones")}
    if cfg.arch_type == "audio":  # whisper: LayerNorm with bias
        s[name + "_b"] = ParamSpec(L + (cfg.d_model,), lx + (None,), init="zeros")
    return s


def _layer_specs(cfg: ModelConfig, n: int, dense_ffn: bool) -> dict:
    s: dict = {}
    s.update(_norm_specs(cfg, n, "ln1"))
    if cfg.arch_type == "ssm":
        s["ssm"] = ssm_lib.ssm_param_specs(cfg, n)
        return s
    s["attn"] = _attn_specs(cfg, n)
    if cfg.arch_type == "hybrid":
        s["ssm"] = ssm_lib.ssm_param_specs(cfg, n)
        s["mix_gate"] = ParamSpec((n, 2), ("layers", None), init="ones")
    s.update(_norm_specs(cfg, n, "ln2"))
    s["ffn"] = _ffn_specs(cfg, n, dense_ffn)
    if cfg.is_encoder_decoder:
        s["cross"] = _attn_specs(cfg, n, cross=True)
        s.update(_norm_specs(cfg, n, "ln_cross"))
    return s


MAMBA, ATTENTION = "mamba", "attention"
# each mixer kind's parameter group under ``layers``, stacked over the
# layers of that kind; the norms and the FFN stack over every layer
KIND_GROUP = {MAMBA: "mamba", ATTENTION: "attn"}


def _kind_layer_specs(cfg: ModelConfig) -> dict:
    n, kinds = cfg.n_layers, cfg.layer_types
    s: dict = {}
    s.update(_norm_specs(cfg, n, "ln1"))
    s.update(_norm_specs(cfg, n, "ln2"))
    s["ffn"] = _ffn_specs(cfg, n, dense=False)
    if MAMBA in kinds:
        s["mamba"] = ssm_lib.ssm_param_specs(cfg, kinds.count(MAMBA))
    if ATTENTION in kinds:
        s["attn"] = _attn_specs(cfg, kinds.count(ATTENTION))
    return s


def kind_runs(cfg: ModelConfig) -> list:
    """[(start, end, kind, first)] for the maximal runs of one mixer kind
    in ``cfg.layer_types``; ``first`` is the run's first index among the
    layers of its kind (its row in that kind's parameters and cache)."""
    runs, seen = [], {MAMBA: 0, ATTENTION: 0}
    for start, end, kind in window_segments(list(cfg.layer_types)):
        runs.append((start, end, kind, seen[kind]))
        seen[kind] += end - start
    return runs


def _layer_params(layers: dict, i, j, kind: str) -> dict:
    """Layer ``i``'s parameters: the norms and FFN at row ``i``, its
    mixer at row ``j`` of its kind's stack. Indexed inside the layer scan,
    so no run's weights are sliced out into a copy."""

    def row(k):
        return lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False)

    lp = {name: jax.tree.map(row(i), v)
          for name, v in layers.items() if name not in KIND_GROUP.values()}
    lp[KIND_GROUP[kind]] = jax.tree.map(row(j), layers[KIND_GROUP[kind]])
    return lp


def _run_ids(start: int, end: int, first: int):
    """(layer ids, ids within the kind) of the run [start, end)."""
    return (jnp.arange(start, end, dtype=jnp.int32),
            jnp.arange(first, first + end - start, dtype=jnp.int32))


def _n_prologue(cfg: ModelConfig) -> int:
    return cfg.moe.first_moe_layer if cfg.moe is not None else 0


def param_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    n_pro = _n_prologue(cfg)
    specs: dict = {
        "embed": ParamSpec((cfg.vocab, d), ("vocab", "embed"), scale=d**0.5),
        "final_norm": ParamSpec((d,), (None,), init="ones"),
        "layers": (
            _kind_layer_specs(cfg) if cfg.layer_types is not None
            else _layer_specs(cfg, cfg.n_layers - n_pro, dense_ffn=False)
        ),
    }
    if cfg.arch_type == "audio":
        specs["final_norm_b"] = ParamSpec((d,), (None,), init="zeros")
    if not cfg.tie_embeddings:
        specs["head"] = ParamSpec((d, cfg.vocab), ("embed", "vocab"))
    if n_pro:
        specs["prologue"] = _layer_specs(cfg, n_pro, dense_ffn=True)
    if cfg.is_encoder_decoder:
        enc = {
            "layers": {
                k: v
                for k, v in _layer_specs(cfg, cfg.n_encoder_layers, True).items()
                if not k.startswith("ln_cross") and k != "cross"
            },
            "final_norm": ParamSpec((d,), (None,), init="ones"),
            "final_norm_b": ParamSpec((d,), (None,), init="zeros"),
        }
        specs["encoder"] = enc
    return specs


# ---------------------------------------------------------------------------
# Layer metadata (per-layer attention window)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig, n_layers: int, offset: int = 0) -> list:
    """Per-layer STATIC attention window (None = global attention).

    Static (trace-time) windows let the stack be applied as one lax.scan
    per contiguous same-window segment, so sliding-window layers compile a
    KV-sliced attention body (O(S·W)) instead of masking an O(S²) grid.
    """
    w = []
    globals_ = {0, cfg.n_layers // 2, cfg.n_layers - 1}
    for i in range(offset, offset + n_layers):
        if cfg.sliding_window is not None and i not in globals_:
            w.append(cfg.sliding_window)
        else:
            w.append(None)
    return w


def window_segments(windows: list) -> list:
    """[(start, end, window)] for maximal same-window runs."""
    segs = []
    start = 0
    for i in range(1, len(windows) + 1):
        if i == len(windows) or windows[i] != windows[start]:
            segs.append((start, i, windows[start]))
            start = i
    return segs


# ---------------------------------------------------------------------------
# Norm dispatch (rms vs whisper layer-norm)
# ---------------------------------------------------------------------------


def _norm(cfg: ModelConfig, lp: dict, name: str, x: jax.Array) -> jax.Array:
    if cfg.arch_type == "audio":
        return layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)
    return rms_norm(x, lp[name], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Attention application (full sequence)
# ---------------------------------------------------------------------------


def _qkv(cfg: ModelConfig, ap: dict, x: jax.Array, kv_x: jax.Array):
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    dt = x.dtype
    q = jnp.einsum("bsd,de->bse", x, ap["wq"].astype(dt))
    k = jnp.einsum("bsd,de->bse", kv_x, ap["wk"].astype(dt))
    v = jnp.einsum("bsd,de->bse", kv_x, ap["wv"].astype(dt))
    if "bq" in ap:
        q = q + ap["bq"].astype(dt)
        k = k + ap["bk"].astype(dt)
        v = v + ap["bv"].astype(dt)
    q = q.reshape(*x.shape[:2], hq, hd)
    k = k.reshape(*kv_x.shape[:2], hkv, hd)
    v = v.reshape(*kv_x.shape[:2], hkv, hd)
    if "q_norm" in ap:
        q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
        k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
    return q, k, v


def _rope_qk(cfg: ModelConfig, q, k, positions, pos3):
    if cfg.rope_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_type == "mrope":
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    return q, k


def attn_full(
    ctx: ApplyCtx, ap: dict, x, positions, pos3, window, causal=True
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    cfg = ctx.cfg
    q, k, v = _qkv(cfg, ap, x, x)
    q, k = _rope_qk(cfg, q, k, positions, pos3)
    out = attention(
        q, k, v, positions, positions, causal=causal, window=window,
        rcfg=ctx.rcfg, scale=cfg.attention_multiplier,
    )
    out = out.reshape(*x.shape[:2], -1)
    return jnp.einsum("bse,ed->bsd", out, ap["wo"].astype(x.dtype)), (k, v)


def cross_attn_full(ctx, ap, x, enc_out, enc_pos):
    cfg = ctx.cfg
    q, k, v = _qkv(cfg, ap, x, enc_out)
    b, s = x.shape[:2]
    qpos = jnp.zeros((b, s), jnp.int32)
    out = attention(q, k, v, qpos, enc_pos, causal=False, rcfg=ctx.rcfg)
    out = out.reshape(b, s, -1)
    return jnp.einsum("bse,ed->bsd", out, ap["wo"].astype(x.dtype)), (k, v)


# ---------------------------------------------------------------------------
# Full-sequence layer body (train / prefill)
# ---------------------------------------------------------------------------


def layer_full(
    ctx: ApplyCtx,
    lp: dict,
    window,
    h: jax.Array,
    positions,
    pos3,
    enc_out=None,
    enc_pos=None,
    want_cache: bool = False,
):
    cfg = ctx.cfg
    cache: Dict[str, jax.Array] = {}
    aux = jnp.zeros((), jnp.float32)
    hn = _norm(cfg, lp, "ln1", h)
    if cfg.arch_type == "ssm":
        out, state, tail = ssm_lib.mamba2_forward(cfg, lp["ssm"], hn, ctx.rcfg)
        if want_cache:
            cache["ssm"] = state.astype(jnp.bfloat16)
            cache["conv"] = tail.astype(jnp.bfloat16)
        return h + _residual(cfg, out), cache, aux
    if cfg.mla is not None:
        with jax.named_scope("attn"):
            attn_out, (latent, krope) = mla_full(
                cfg, lp["attn"], hn, positions, ctx.rcfg
            )
        if want_cache:
            with jax.named_scope("kv_write"):
                cache["ckv"] = _to_cache("ckv", latent.astype(jnp.bfloat16))
                cache["krope"] = _to_cache("krope", krope.astype(jnp.bfloat16))
    else:
        with jax.named_scope("attn"):
            attn_out, (k, v) = attn_full(
                ctx, lp["attn"], hn, positions, pos3, window, causal=True
            )
        if want_cache:
            with jax.named_scope("kv_write"):
                cache["k"] = _to_cache("k", k.astype(jnp.bfloat16))
                cache["v"] = _to_cache("v", v.astype(jnp.bfloat16))
    if cfg.arch_type == "hybrid":
        ssm_out, state, tail = ssm_lib.mamba2_forward(cfg, lp["ssm"], hn, ctx.rcfg)
        g = jax.nn.sigmoid(lp["mix_gate"].astype(jnp.float32))
        attn_out = (g[0] * attn_out + g[1] * ssm_out).astype(hn.dtype)
        if want_cache:
            cache["ssm"] = state.astype(jnp.bfloat16)
            cache["conv"] = tail.astype(jnp.bfloat16)
    h = h + _residual(cfg, attn_out)
    if cfg.is_encoder_decoder and enc_out is not None:
        hc = _norm(cfg, lp, "ln_cross", h)
        c_out, (ck, cv) = cross_attn_full(ctx, lp["cross"], hc, enc_out, enc_pos)
        h = h + c_out
        if want_cache:
            cache["cross_k"] = ck.astype(jnp.bfloat16)
            cache["cross_v"] = cv.astype(jnp.bfloat16)
    hn2 = _norm(cfg, lp, "ln2", h)
    with jax.named_scope("mlp"):
        ff, aux = _ffn(ctx, lp["ffn"], hn2)
    return h + _residual(cfg, ff), cache, aux


def _residual(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """A mixer's or FFN's output as it joins the residual stream."""
    return x * cfg.residual_multiplier if cfg.residual_multiplier != 1.0 else x


def _ffn_scope(fp: dict) -> str:
    return "moe" if "router" in fp else "mlp"


def kind_layer_full(ctx: ApplyCtx, kind: str, lp: dict, h, positions, cache, i):
    """One layer of a stack with per-layer kinds, over the whole sequence:
    a Mamba-2 or an attention mixer, then the FFN. With ``cache`` (the
    stacked leaves, as ``abstract_cache`` lays them out), the layer
    writes its state at row ``i`` of its kind's leaves. Returns (h, aux)."""
    cfg = ctx.cfg
    hn = _norm(cfg, lp, "ln1", h)
    if kind == MAMBA:
        with jax.named_scope("ssm"):
            out, state, tail = ssm_lib.mamba2_forward(cfg, lp["mamba"], hn, ctx.rcfg)
        if cache is not None:
            with jax.named_scope("ssm_write"):
                _write(cache, "ssm", i, state)
                _write(cache, "conv", i, tail)
    else:
        with jax.named_scope("attn"):
            out, (k, v) = attn_full(ctx, lp["attn"], hn, positions, None, None)
        if cache is not None:
            with jax.named_scope("kv_write"):
                _write(cache, "k", i, k, 0)
                _write(cache, "v", i, v, 0)
    h = h + _residual(cfg, out)
    hn2 = _norm(cfg, lp, "ln2", h)
    with jax.named_scope(_ffn_scope(lp["ffn"])):
        ff, aux = _ffn(ctx, lp["ffn"], hn2)
    return h + _residual(cfg, ff), aux


def run_kinds(ctx: ApplyCtx, layers: dict, h, positions, cache=None):
    """A stack with per-layer kinds over the whole sequence: one
    ``lax.scan`` per maximal run of one kind, a run of one layer called
    directly. ``cache``: zeroed leaves that each layer writes in place.
    Returns (h, aux, cache)."""
    aux = jnp.zeros((), jnp.float32)
    for start, end, kind, first in kind_runs(ctx.cfg):

        def body(carry, xs, _kind=kind):
            hh, aux_c, c = carry
            i, j = xs
            c = None if c is None else dict(c)
            lp = _layer_params(layers, i, j, _kind)
            hh, aux_l = kind_layer_full(ctx, _kind, lp, hh, positions, c, j)
            return (constrain_batch(ctx, hh), aux_c + aux_l, c), None

        body = _maybe_remat(ctx.rcfg, body)
        if end - start == 1:
            (h, aux, cache), _ = body((h, aux, cache), (start, first))
        else:
            (h, aux, cache), _ = jax.lax.scan(
                body, (h, aux, cache), _run_ids(start, end, first))
    return h, aux, cache


def _ffn(ctx: ApplyCtx, fp: dict, hn2: jax.Array):
    """The layer's feed-forward block: (output, MoE auxiliary loss)."""
    cfg = ctx.cfg
    if "router" in fp:
        return moe_lib.moe_ffn(cfg, ctx.rcfg, ctx.mesh, fp, hn2)
    if cfg.arch_type == "audio":
        from repro.models.layers import gelu_mlp

        ff = gelu_mlp(hn2, fp["wg"], fp["bg"], fp["wd"], fp["bd"])
    else:
        ff = swiglu(hn2, fp["wg"], fp["wu"], fp["wd"])
    return ff, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Stack application
# ---------------------------------------------------------------------------


def _maybe_remat(rcfg: RunConfig, fn):
    if rcfg.remat == "none":
        return fn
    if rcfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


def run_stack(
    ctx: ApplyCtx,
    stack_params: dict,
    windows: jax.Array,
    h: jax.Array,
    positions,
    pos3,
    enc_out=None,
    enc_pos=None,
    want_cache: bool = False,
):
    aux = jnp.zeros((), jnp.float32)
    seg_caches = []
    for start, end, win in window_segments(windows):
        seg_params = jax.tree.map(lambda a: a[start:end], stack_params)

        def body(carry, lp, _win=win):
            hh, aux_c = carry
            hh, cache, aux_l = layer_full(
                ctx, lp, _win, hh, positions, pos3, enc_out, enc_pos, want_cache
            )
            hh = constrain_batch(ctx, hh)
            return (hh, aux_c + aux_l), cache

        body = _maybe_remat(ctx.rcfg, body)
        (h, aux), cache = jax.lax.scan(body, (h, aux), seg_params)
        seg_caches.append(cache)
    if want_cache and seg_caches:
        with jax.named_scope("kv_write"):
            caches = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *seg_caches
            )
    else:
        caches = seg_caches[0] if seg_caches else {}
    return h, aux, caches


def run_prologue(ctx, pro_params, windows, h, positions, pos3, want_cache):
    """Unrolled leading layers (dense FFN before the MoE stack)."""
    n = len(windows)
    caches = []
    aux = jnp.zeros((), jnp.float32)
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], pro_params)
        h, cache, aux_l = layer_full(
            ctx, lp, windows[i], h, positions, pos3, None, None, want_cache
        )
        caches.append(cache)
        aux = aux + aux_l
    if want_cache and caches:
        with jax.named_scope("kv_write"):
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    else:
        caches = {}
    return h, aux, caches


# ---------------------------------------------------------------------------
# Embedding / positions
# ---------------------------------------------------------------------------


def sinusoidal_pos(positions: jax.Array, d: int) -> jax.Array:
    """(B,S) int -> (B,S,d) sinusoidal embedding (computed, not a table)."""
    half = d // 2
    dim = jnp.arange(half, dtype=jnp.float32)
    inv = jnp.exp(-jnp.log(10_000.0) * dim / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def build_mrope_positions(b: int, s: int, n_vision: int, offset=0) -> jax.Array:
    """(3,B,S) t/h/w position ids: vision patches on a 16-wide grid at t=0,
    text tokens advance t beyond the vision span."""
    idx = jnp.arange(s)
    is_vis = idx < n_vision
    t = jnp.where(is_vis, 0, idx - n_vision + 1)
    hh = jnp.where(is_vis, idx // 16, t)
    ww = jnp.where(is_vis, idx % 16, t)
    pos = jnp.stack([t, hh, ww]).astype(jnp.int32) + offset
    return jnp.broadcast_to(pos[:, None, :], (3, b, s))


def embed(ctx: ApplyCtx, params, tokens, positions, vision_embeds=None):
    cfg = ctx.cfg
    h = params["embed"][tokens].astype(ctx.rcfg.cdtype)
    if vision_embeds is not None and cfg.n_vision_tokens:
        nv = vision_embeds.shape[1]
        h = jnp.concatenate([vision_embeds.astype(h.dtype), h[:, nv:]], axis=1)
    if cfg.rope_type == "none" and cfg.arch_type != "ssm":
        h = h + sinusoidal_pos(positions, cfg.d_model).astype(h.dtype)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return constrain_batch(ctx, h)


def unembed(ctx: ApplyCtx, params, h):
    cfg = ctx.cfg
    if cfg.arch_type == "audio":
        h = layer_norm(h, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    else:
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def encode(ctx: ApplyCtx, params, enc_feats):
    """Whisper encoder over stub frame embeddings (B, T_enc, d)."""
    cfg = ctx.cfg
    b, t, _ = enc_feats.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    h = enc_feats.astype(ctx.rcfg.cdtype)
    h = h + sinusoidal_pos(pos, cfg.d_model).astype(h.dtype)
    def body(carry, lp):
        hh, _ = carry
        hn = _norm(cfg, lp, "ln1", hh)
        # bidirectional self-attention
        q, k, v = _qkv(cfg, lp["attn"], hn, hn)
        out = attention(q, k, v, pos, pos, causal=False, rcfg=ctx.rcfg)
        out = out.reshape(b, t, -1)
        hh = hh + jnp.einsum("bse,ed->bsd", out, lp["attn"]["wo"].astype(hh.dtype))
        hn2 = _norm(cfg, lp, "ln2", hh)
        from repro.models.layers import gelu_mlp

        fp = lp["ffn"]
        hh = hh + gelu_mlp(hn2, fp["wg"], fp["bg"], fp["wd"], fp["bd"])
        return (constrain_batch(ctx, hh), jnp.zeros((), jnp.float32)), None

    body = _maybe_remat(ctx.rcfg, body)
    (h, _), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                             params["encoder"]["layers"])
    h = layer_norm(h, params["encoder"]["final_norm"],
                   params["encoder"]["final_norm_b"], cfg.norm_eps)
    return h, pos


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward_train(ctx: ApplyCtx, params, batch) -> Tuple[jax.Array, jax.Array]:
    """batch: tokens (B,S) [+ vision_embeds | enc_feats] -> (logits, aux)."""
    cfg = ctx.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    pos3 = (
        build_mrope_positions(b, s, cfg.n_vision_tokens)
        if cfg.rope_type == "mrope"
        else None
    )
    enc_out = enc_pos = None
    if cfg.is_encoder_decoder:
        enc_out, enc_pos = encode(ctx, params, batch["enc_feats"])
    h = embed(ctx, params, tokens, positions, batch.get("vision_embeds"))
    if cfg.layer_types is not None:
        h, aux, _ = run_kinds(ctx, params["layers"], h, positions)
        return unembed(ctx, params, h), aux
    n_pro = _n_prologue(cfg)
    aux = jnp.zeros((), jnp.float32)
    if n_pro:
        h, aux_p, _ = run_prologue(
            ctx, params["prologue"], layer_windows(cfg, n_pro), h, positions,
            pos3, False,
        )
        aux = aux + aux_p
    h, aux_m, _ = run_stack(
        ctx, params["layers"], layer_windows(cfg, cfg.n_layers - n_pro, n_pro),
        h, positions, pos3, enc_out, enc_pos, False,
    )
    aux = aux + aux_m
    return unembed(ctx, params, h), aux


def prefill(ctx: ApplyCtx, params, batch, capacity: Optional[int] = None):
    """Fill a KV cache over the whole prompt. Returns (cache, last_logits).

    ``capacity`` reserves extra slots for subsequent decode steps (defaults
    to the prompt length; decode then ring-overwrites the oldest slots,
    which is only correct for pure sliding-window attention).
    """
    cfg = ctx.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    pos3 = (
        build_mrope_positions(b, s, cfg.n_vision_tokens)
        if cfg.rope_type == "mrope"
        else None
    )
    enc_out = enc_pos = None
    cache: Dict[str, Any] = {}
    if cfg.is_encoder_decoder:
        enc_out, enc_pos = encode(ctx, params, batch["enc_feats"])
    with jax.named_scope("embed"):
        h = embed(ctx, params, tokens, positions, batch.get("vision_embeds"))
    if cfg.layer_types is not None:
        # the ring is sized to its capacity from the start: each layer
        # writes its K/V or state into the zeroed leaves in place
        leaves = abstract_cache(cfg, b, max(capacity or s, s))["main"]
        zeros = {k: jnp.zeros(a.shape, a.dtype) for k, a in leaves.items()}
        h, _, cache["main"] = run_kinds(ctx, params["layers"], h, positions, zeros)
        cache["length"] = jnp.asarray(s, jnp.int32)
        with jax.named_scope("head"):
            logits = unembed(ctx, params, h[:, -1:])
        return cache, logits
    n_pro = _n_prologue(cfg)
    if n_pro:
        h, _, c_pro = run_prologue(
            ctx, params["prologue"], layer_windows(cfg, n_pro), h, positions,
            pos3, True,
        )
        cache["pro"] = c_pro
    h, _, c_main = run_stack(
        ctx, params["layers"], layer_windows(cfg, cfg.n_layers - n_pro, n_pro),
        h, positions, pos3, enc_out, enc_pos, True,
    )
    cache["main"] = c_main
    if capacity is not None and capacity > s:
        pad = capacity - s

        def pad_seq(path, leaf):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            if name in SLOT_AXIS:
                width = [(0, 0)] * leaf.ndim
                width[SLOT_AXIS[name]] = (0, pad)  # grow the ring
                return jnp.pad(leaf, width)
            return leaf

        with jax.named_scope("kv_write"):
            cache = jax.tree_util.tree_map_with_path(pad_seq, cache)
    cache["length"] = jnp.asarray(s, jnp.int32)
    with jax.named_scope("head"):
        logits = unembed(ctx, params, h[:, -1:])
    return cache, logits


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _ring_kv_pos(length: jax.Array, w: int) -> jax.Array:
    """Positions currently held by each ring slot after writing pos=length.

    Slot s holds position p = length - ((length - s) mod W); invalid (never
    written) slots yield negative p.
    """
    s = jnp.arange(w, dtype=jnp.int32)
    p = length - ((length - s) % w)
    return p  # p in (length-W, length]; p<0 marks unwritten slots


def abstract_cache(
    cfg: ModelConfig, batch: int, w: int, enc_len: Optional[int] = None
) -> dict:
    """ShapeDtypeStruct cache pytree (capacity ``w`` per attention layer).

    Each leaf stacks its layers first: K/V ``(L, B, kv_heads, w,
    head_dim)``, MLA latent ``(L, B, w, r)``, SSM state ``(L, B, heads,
    headdim, d_state)``, conv tail ``(L, B, d_conv - 1, conv_dim)``, cross
    K/V ``(L, B, enc_len, kv_heads, head_dim)``; ``length`` is a scalar."""

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def layer_cache(n: int) -> dict:
        c: Dict[str, Any] = {}
        hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
        if cfg.arch_type != "ssm":
            if cfg.mla is not None:
                c["ckv"] = sds((n, batch, w, cfg.mla.kv_lora_rank))
                c["krope"] = sds((n, batch, w, cfg.mla.qk_rope_head_dim))
            else:
                c["k"] = sds((n, batch, hkv, w, hd))
                c["v"] = sds((n, batch, hkv, w, hd))
        if cfg.arch_type in ("ssm", "hybrid"):
            s = cfg.ssm
            nh = s.n_ssm_heads(cfg.d_model)
            conv_dim = s.inner(cfg.d_model) + 2 * s.d_state
            c["ssm"] = sds((n, batch, nh, s.headdim, s.d_state))
            c["conv"] = sds((n, batch, s.d_conv - 1, conv_dim))
        if cfg.is_encoder_decoder:
            el = enc_len or cfg.encoder_seq_len
            c["cross_k"] = sds((n, batch, el, hkv, hd))
            c["cross_v"] = sds((n, batch, el, hkv, hd))
        return c

    if cfg.layer_types is not None:  # each leaf over the layers of its kind
        n_attn = cfg.layer_types.count(ATTENTION)
        n_mamba = cfg.layer_types.count(MAMBA)
        c: Dict[str, Any] = {}
        if n_attn:
            hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
            c["k"] = sds((n_attn, batch, hkv, w, hd))
            c["v"] = sds((n_attn, batch, hkv, w, hd))
        if n_mamba:
            s = cfg.ssm
            conv_dim = s.inner(cfg.d_model) + 2 * s.d_state
            c["ssm"] = sds((n_mamba, batch, s.n_ssm_heads(cfg.d_model), s.headdim,
                            s.d_state))
            c["conv"] = sds((n_mamba, batch, s.d_conv - 1, conv_dim))
        return {"main": c, "length": jax.ShapeDtypeStruct((), jnp.int32)}
    n_pro = _n_prologue(cfg)
    cache: Dict[str, Any] = {"main": layer_cache(cfg.n_layers - n_pro)}
    if n_pro:
        cache["pro"] = layer_cache(n_pro)
    cache["length"] = jax.ShapeDtypeStruct((), jnp.int32)
    return cache


def _layer_slab(cache: dict, name: str, i) -> jax.Array:
    """Layer ``i`` of the stacked leaf ``cache[name]``, as (B, ...)."""
    slab = jax.lax.dynamic_index_in_dim(cache[name], i, 0, keepdims=False)
    return _from_cache(name, slab) if name in SLOT_AXIS else slab


def _write(cache: dict, name: str, i, value: jax.Array, slot=None) -> None:
    """Write ``value`` (B, ...) into layer ``i`` of the stacked leaf
    ``cache[name]``: one token at ring ``slot`` of a ring leaf or, with no
    slot, the layer's whole state. The leaf is a loop carry, so XLA
    updates it in place. The carry is held to the caller's row-major
    layout: left free, the TPU compiler lays a ring out slot-major for the
    token write and then copies the whole cache in and out of the loop."""
    leaf = cache[name]
    start = [i] + [0] * (leaf.ndim - 1)
    if slot is not None:
        value, start[SLOT_AXIS[name]] = _to_cache(name, value), slot
    new = jax.lax.dynamic_update_slice(leaf, value[None].astype(leaf.dtype), start)
    cache[name] = with_layout_constraint(
        new, Layout(major_to_minor=tuple(range(new.ndim)))
    )


def layer_decode(ctx: ApplyCtx, lp, window, cache, i, h, pos, pos3):
    """One-token decode through layer ``i`` of a stack.

    ``cache`` holds the stack's leaves, stacked over its layers as
    ``abstract_cache`` lays them out. The token's K/V (or latent) is
    written at its ring slot and the SSM/conv state replaced; attention
    then reads the layer's slab from the updated leaf. Returns (h, cache)."""
    cfg = ctx.cfg
    b = h.shape[0]
    t = pos[0, 0]  # scalar position (batch-aligned serving)
    hn = _norm(cfg, lp, "ln1", h)
    cache = dict(cache)

    def ssm_decode():
        out, st, cv = ssm_lib.mamba2_decode(
            cfg, lp["ssm"], hn, _layer_slab(cache, "ssm", i).astype(jnp.float32),
            _layer_slab(cache, "conv", i).astype(hn.dtype),
        )
        _write(cache, "ssm", i, st)
        _write(cache, "conv", i, cv)
        return out

    if cfg.arch_type == "ssm":
        return h + _residual(cfg, ssm_decode()), cache

    if cfg.mla is not None:
        from repro.models.mla import _latent  # shared projection helper

        with jax.named_scope("attn"):
            latent, krope = _latent(cfg, lp["attn"], hn, pos)
        w = cache["ckv"].shape[SLOT_AXIS["ckv"]]
        slot = t % w
        with jax.named_scope("kv_write"):
            _write(cache, "ckv", i, latent, slot)
            _write(cache, "krope", i, krope, slot)
        with jax.named_scope("attn"):
            kv_pos = jnp.broadcast_to(_ring_kv_pos(t, w), (b, w))
            attn_out = mla_decode(
                cfg, lp["attn"], hn, pos,
                _layer_slab(cache, "ckv", i).astype(hn.dtype),
                _layer_slab(cache, "krope", i).astype(hn.dtype), kv_pos,
            )
    else:
        attn_out = _attn_decode(ctx, lp["attn"], hn, cache, i, pos, pos3, window)

    if cfg.arch_type == "hybrid":
        g = jax.nn.sigmoid(lp["mix_gate"].astype(jnp.float32))
        attn_out = (g[0] * attn_out + g[1] * ssm_decode()).astype(hn.dtype)

    h = h + _residual(cfg, attn_out)

    if cfg.is_encoder_decoder:
        hc = _norm(cfg, lp, "ln_cross", h)
        ck = _layer_slab(cache, "cross_k", i).astype(hn.dtype)
        cv_ = _layer_slab(cache, "cross_v", i).astype(hn.dtype)
        el = ck.shape[1]
        q, _, _ = _qkv(cfg, lp["cross"], hc, hc)
        enc_pos = jnp.broadcast_to(jnp.arange(el, dtype=jnp.int32), (b, el))
        out = attention(q, ck, cv_, jnp.zeros((b, 1), jnp.int32), enc_pos,
                        causal=False, rcfg=ctx.rcfg)
        out = out.reshape(b, 1, -1)
        h = h + jnp.einsum("bse,ed->bsd", out, lp["cross"]["wo"].astype(hn.dtype))

    hn2 = _norm(cfg, lp, "ln2", h)
    with jax.named_scope("mlp"):
        ff, _ = _ffn(ctx, lp["ffn"], hn2)
    return h + _residual(cfg, ff), cache


def _attn_decode(ctx: ApplyCtx, ap: dict, hn, cache: dict, i, pos, pos3, window):
    """One token's GQA attention through layer ``i``: its K/V written at
    the ring slot of ``cache`` (a dict updated in place), then attention
    over the layer's updated slab and the output projection."""
    cfg = ctx.cfg
    b = hn.shape[0]
    t = pos[0, 0]
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, ap, hn, hn)
        q, k = _rope_qk(cfg, q, k, pos, pos3)
    w = cache["k"].shape[SLOT_AXIS["k"]]
    slot = t % w
    with jax.named_scope("kv_write"):
        _write(cache, "k", i, k, slot)
        _write(cache, "v", i, v, slot)
    with jax.named_scope("attn"):
        kv_pos = jnp.broadcast_to(_ring_kv_pos(t, w), (b, w))
        attn_out = attention(
            q, _layer_slab(cache, "k", i).astype(hn.dtype),
            _layer_slab(cache, "v", i).astype(hn.dtype), pos, kv_pos,
            causal=True, window=window, rcfg=ctx.rcfg,
            scale=cfg.attention_multiplier,
        )
        attn_out = attn_out.reshape(b, 1, -1)
        return jnp.einsum("bse,ed->bsd", attn_out, ap["wo"].astype(hn.dtype))


def kind_layer_decode(ctx: ApplyCtx, kind: str, lp: dict, cache: dict, i, h, pos):
    """One-token decode through layer ``i`` of its kind: the Mamba-2
    state and conv tail replaced in place, or the token's K/V written at
    its ring slot; then the FFN. Returns (h, cache)."""
    cfg = ctx.cfg
    hn = _norm(cfg, lp, "ln1", h)
    cache = dict(cache)
    if kind == MAMBA:
        with jax.named_scope("ssm"):
            out, st, cv = ssm_lib.mamba2_decode(
                cfg, lp["mamba"], hn,
                _layer_slab(cache, "ssm", i).astype(jnp.float32),
                _layer_slab(cache, "conv", i).astype(hn.dtype),
            )
        with jax.named_scope("ssm_write"):
            _write(cache, "ssm", i, st)
            _write(cache, "conv", i, cv)
    else:
        out = _attn_decode(ctx, lp["attn"], hn, cache, i, pos, None, None)
    h = h + _residual(cfg, out)
    hn2 = _norm(cfg, lp, "ln2", h)
    with jax.named_scope(_ffn_scope(lp["ffn"])):
        ff, _ = _ffn(ctx, lp["ffn"], hn2)
    return h + _residual(cfg, ff), cache


def decode_step(ctx: ApplyCtx, params, cache, tokens):
    """One decode step: tokens (B,1) + cache -> (new cache, logits (B,1,V)).

    The stacked cache leaves ride the layer scan as its carry, so a jit
    that donates them updates the cache in place."""
    cfg = ctx.cfg
    b = tokens.shape[0]
    t = cache["length"]
    pos = jnp.broadcast_to(t, (b, 1)).astype(jnp.int32)
    # M-RoPE: text positions advance from 1 past the vision span (matching
    # build_mrope_positions), not from the raw cache index.
    t3 = t - cfg.n_vision_tokens + 1 if cfg.n_vision_tokens else t
    pos3 = (
        jnp.broadcast_to(t3, (3, b, 1)).astype(jnp.int32)
        if cfg.rope_type == "mrope"
        else None
    )
    with jax.named_scope("embed"):
        h = embed(ctx, params, tokens, pos, None)
    new_cache = dict(cache)
    if cfg.layer_types is not None:
        stack = cache["main"]
        layers = params["layers"]
        for start, end, kind, first in kind_runs(cfg):

            def kind_body(carry, xs, _kind=kind):
                hh, kv = carry
                i, j = xs
                lp = _layer_params(layers, i, j, _kind)
                hh, kv = kind_layer_decode(ctx, _kind, lp, kv, j, hh, pos)
                return (constrain_batch(ctx, hh), kv), None

            if end - start == 1:
                (h, stack), _ = kind_body((h, stack), (start, first))
            else:
                (h, stack), _ = jax.lax.scan(
                    kind_body, (h, stack), _run_ids(start, end, first))
        new_cache["main"] = stack
        new_cache["length"] = t + 1
        with jax.named_scope("head"):
            logits = unembed(ctx, params, h)
        return new_cache, logits
    n_pro = _n_prologue(cfg)
    if n_pro:
        windows = layer_windows(cfg, n_pro)
        for i in range(n_pro):
            lp = jax.tree.map(lambda a: a[i], params["prologue"])
            h, new_cache["pro"] = layer_decode(
                ctx, lp, windows[i], new_cache["pro"], i, h, pos, pos3
            )

    windows = layer_windows(cfg, cfg.n_layers - n_pro, n_pro)
    stack = cache["main"]
    for start, end, win in window_segments(windows):
        seg_params = jax.tree.map(lambda a: a[start:end], params["layers"])

        def body(carry, xs, _win=win):
            hh, kv = carry
            lp, i = xs
            hh, kv = layer_decode(ctx, lp, _win, kv, i, hh, pos, pos3)
            return (constrain_batch(ctx, hh), kv), None

        layer_ids = jnp.arange(start, end, dtype=jnp.int32)
        (h, stack), _ = jax.lax.scan(body, (h, stack), (seg_params, layer_ids))
    new_cache["main"] = stack
    new_cache["length"] = t + 1
    with jax.named_scope("head"):
        logits = unembed(ctx, params, h)
    return new_cache, logits


# ---------------------------------------------------------------------------
# Param construction helpers
# ---------------------------------------------------------------------------


def init_model_params(key, cfg: ModelConfig, rcfg: RunConfig):
    return init_params(key, param_specs(cfg), rcfg.pdtype)


def abstract_model_params(cfg: ModelConfig, rcfg: RunConfig):
    return abstract_params(param_specs(cfg), rcfg.pdtype)


def model_param_axes(cfg: ModelConfig):
    return param_axes(param_specs(cfg))
