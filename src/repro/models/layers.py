"""Parameter-spec system + elementary layers.

Single source of truth: every model declares a pytree of ``ParamSpec``
(shape + logical axes + init scale). From that one tree we derive
  * real parameters      (``init_params``)
  * abstract parameters  (``abstract_params`` — ShapeDtypeStruct, no alloc)
  * sharding specs       (``repro.sharding.specs`` maps logical axes -> mesh)
so the dry-run, the trainer and the tests can never drift apart.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | small_normal
    scale: float = 1.0  # stddev multiplier (normal: scale / sqrt(fan_in))

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn, specs: PyTree) -> PyTree:
    return jax.tree.map(fn, specs, is_leaf=_is_spec)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_leaf(k: jax.Array, s: ParamSpec, dtype) -> jax.Array:
    """One leaf, jitted so the float32 draw fuses into the cast: a
    stacked full-width leaf (qwen2.5-3b's FFN: 36×2048×11008) never
    holds a float32 copy in device memory."""
    if s.init == "zeros":
        return jnp.zeros(s.shape, dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, dtype)
    fan_in = s.shape[-2] if len(s.shape) >= 2 else max(s.shape[-1], 1)
    std = s.scale / np.sqrt(fan_in)
    return (jax.random.normal(k, s.shape, jnp.float32) * std).astype(dtype)


def init_params(key: jax.Array, specs: PyTree, dtype=jnp.float32) -> PyTree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(k, s, dtype) for k, s in zip(keys, leaves)]
    )


def abstract_params(specs: PyTree, dtype=jnp.float32) -> PyTree:
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs
    )


def param_axes(specs: PyTree) -> PyTree:
    return tree_map_specs(lambda s: s.axes, specs)


# ---------------------------------------------------------------------------
# Elementary ops (pure functions over arrays)
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, wg.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, wu.astype(x.dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, wd.astype(x.dtype))


def gelu_mlp(x, w1, b1, w2, b2):
    h = jnp.einsum("...d,df->...f", x, w1.astype(x.dtype)) + b1.astype(x.dtype)
    h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, w2.astype(x.dtype)) + b2.astype(x.dtype)


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Whisper-style sinusoidal absolute position embedding table."""
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    inv = np.exp(-np.log(10_000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    tab = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(tab, dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL).

    x: (B, S, H, D); positions: (3, B, S) int32 — temporal/height/width
    position ids. The D/2 rotary frequencies are split into ``sections``
    (t, h, w); each section takes its angle from the matching position id.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (3,B,S,d/2)
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (d/2,)
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang, 0, -1), sec[None, None, :, None], axis=-1
    )[..., 0]  # (B,S,d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
