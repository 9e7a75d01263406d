"""Plain float32 reference of a dense GQA decoder.

RMSNorm, rotary positions (rotate-half), grouped-query attention with an
optional q/k/v bias, a SwiGLU feed-forward block and a head that is
either its own matrix or the embedding, tied. Written from the published
description of Qwen2 / Llama in straightforward ``jax.numpy``; every
matrix product at ``Precision.HIGHEST``, so float32 on a TPU too. It
imports nothing of the program.

It reads the benchmark's weights by these names (the serving program's
layout): ``embed`` (V, d), ``head`` (d, V) unless tied, ``final_norm``,
and under ``layers``, each stacked over the layers: ``ln1``, ``ln2``,
``attn/{wq,wk,wv,wo,bq,bk,bv}``, ``ffn/{wg,wu,wd}``.

It runs layer by layer over blocks of rows, so that a full-width model
fits beside its weights, and returns for each served token how far the
reference's logit of that token lies below the reference's best, and at
each position whose logits the program kept, the largest difference
between those logits and the reference's.

``quant`` gives the control: the same computation with every matrix
rounded to a lower precision first (``int8``: symmetric, one scale per
output channel; ``fp8``: float8_e4m3fn with one scale per output
channel). The control's gaps are those of the tokens that the lower
precision ranks first, and its logit errors those of its own logits.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
POS_CHUNK = 512  # positions per head matmul: bounds the (chunk, V) logits
KEEP_CHUNK = 64  # kept positions compared per call


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _weight(w, quant):
    """A matrix in float32, optionally rounded through ``quant`` first
    (one scale per output column, the last axis)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown quantisation {quant!r}")


def _rope(x, theta):
    """x: (R, L, H, D); positions 0..L-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # (L, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(layers, i, h, cfg, quant):
    d, hq, hkv, eps, theta = cfg
    lp = jax.tree.map(lambda a: a[i], layers)
    at, ff = lp["attn"], lp["ffn"]
    r, length, _ = h.shape
    hd = d // hq
    x = _rms(h, lp["ln1"].astype(jnp.float32), eps)
    q = _mm("rld,de->rle", x, _weight(at["wq"], quant))
    k = _mm("rld,de->rle", x, _weight(at["wk"], quant))
    v = _mm("rld,de->rle", x, _weight(at["wv"], quant))
    if at.get("bq") is not None:  # q/k/v bias (Qwen2); Llama has none
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q = _rope(q.reshape(r, length, hq, hd), theta)
    k = _rope(k.reshape(r, length, hkv, hd), theta)
    v = v.reshape(r, length, hkv, hd)
    # head j reads kv head j // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = _mm("rqhd,rkhd->rhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((length, length), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("rhqk,rkhd->rqhd", p, v).reshape(r, length, hq * hd)
    h = h + _mm("rle,ed->rld", o, _weight(at["wo"], quant))
    x = _rms(h, lp["ln2"].astype(jnp.float32), eps)
    g = _mm("rld,df->rlf", x, _weight(ff["wg"], quant))
    u = _mm("rld,df->rlf", x, _weight(ff["wu"], quant))
    return h + _mm("rlf,fd->rld", jax.nn.silu(g) * u, _weight(ff["wd"], quant))


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, quant):
    # an embedding row is a column of the tied head: round it the same way
    w = _weight(table.T, quant).T if quant else table.astype(jnp.float32)
    return w[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(h, norm, eps):
    return _rms(h, norm.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gaps(head, ref_h, other_h, tokens, quant):
    """Per position: reference best − reference logit of the chosen token.
    The chosen token is ``tokens`` when ``other_h`` is None, else the
    argmax of the logits that ``other_h`` gives under ``quant``."""
    ref = _mm("ld,dv->lv", ref_h, head.astype(jnp.float32))
    if other_h is not None:
        tokens = jnp.argmax(_mm("ld,dv->lv", other_h, _weight(head, quant)), -1)
    picked = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked


@jax.jit
def _errors_of(head, ref_h, logits):
    """Per position: the largest |logit − reference logit| over the vocabulary."""
    ref = _mm("kd,dv->kv", ref_h, head.astype(jnp.float32))
    return jnp.max(jnp.abs(logits.astype(jnp.float32) - ref), axis=-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _errors_of_control(head, ref_h, other_h, quant):
    ref = _mm("kd,dv->kv", ref_h, head.astype(jnp.float32))
    other = _mm("kd,dv->kv", other_h, _weight(head, quant))
    return jnp.max(jnp.abs(other - ref), axis=-1)


def _arch(config: dict) -> Tuple:
    return (
        int(config["hidden_size"]),
        int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]),
        float(config["rms_norm_eps"]),
        float(config["rope_theta"]),
    )


def _head(weights, config):
    return weights["embed"].T if config["tie_word_embeddings"] else weights["head"]


def hidden(weights, config: dict, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (R, L, d) of the rows of ``tokens``."""
    cfg = _arch(config)
    h = _embed(weights["embed"], jnp.asarray(tokens), quant)
    for i in range(int(config["num_hidden_layers"])):
        h = _layer(weights["layers"], jnp.int32(i), h, cfg, quant)
    return _final(h, weights["final_norm"], cfg[3])


def rows_per_block(config: dict, length: int, budget_bytes: float = 1.0e9) -> int:
    """Rows whose float32 attention scores fit in ``budget_bytes``."""
    per_row = 4.0 * int(config["num_attention_heads"]) * length * length
    return max(1, int(budget_bytes // per_row))


def compare(
    weights,
    config: dict,
    served: Sequence[Tuple[np.ndarray, np.ndarray]],
    length: int,
    kept: Sequence[Tuple[np.ndarray, np.ndarray]],
    quant=None,
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """For each (prompt, served tokens) and its kept logits (token indices,
    (K, V) logits the program produced for them): the gap of every served
    token, and the largest logit error at each kept index.

    Served token j is predicted at position len(prompt) - 1 + j of the
    sequence prompt + served[:-1]. Rows are padded to ``length`` (one
    compiled shape); padding sits after every real position and, under
    the causal mask, changes none of them. With ``quant`` the control
    takes the program's place: the tokens judged are its own first
    choices, and the logits compared its own, at the same positions.
    """
    head = _head(weights, config)
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    block = min(rows_per_block(config, length), max(len(served), 1))
    for b0 in range(0, len(served), block):
        part = served[b0 : b0 + block]
        tokens = np.zeros((block, length), np.int32)
        for r, (prompt, toks) in enumerate(part):
            seq = np.concatenate([prompt, toks[:-1]])
            tokens[r, : seq.size] = seq
        ref_h = hidden(weights, config, tokens)
        other_h = hidden(weights, config, tokens, quant) if quant else None
        for r, (prompt, toks) in enumerate(part):
            p0 = prompt.size - 1
            gaps = []
            for c0 in range(0, toks.size, POS_CHUNK):
                # fixed chunk shape: the last chunk is padded, then cut
                seg = toks[c0 : c0 + POS_CHUNK]
                rh = _rows(ref_h[r], p0 + c0 + np.arange(seg.size))
                oh = None if other_h is None else _rows(
                    other_h[r], p0 + c0 + np.arange(seg.size))
                tk = np.zeros(POS_CHUNK, np.int32)
                tk[: seg.size] = seg
                g = _gaps(head, rh, oh, jnp.asarray(tk), quant)
                gaps.append(np.asarray(g)[: seg.size])
            index, logits = kept[b0 + r]
            errors = []
            for c0 in range(0, index.size, KEEP_CHUNK):
                idx = index[c0 : c0 + KEEP_CHUNK]
                rh = _rows(ref_h[r], p0 + idx, KEEP_CHUNK)
                if other_h is None:
                    lg = np.zeros((KEEP_CHUNK, logits.shape[-1]), np.float32)
                    lg[: idx.size] = logits[c0 : c0 + KEEP_CHUNK]
                    e = _errors_of(head, rh, jnp.asarray(lg))
                else:
                    oh = _rows(other_h[r], p0 + idx, KEEP_CHUNK)
                    e = _errors_of_control(head, rh, oh, quant)
                errors.append(np.asarray(e)[: idx.size])
            out.append((np.concatenate(gaps), np.concatenate(errors or [np.zeros(0)])))
    return out


def _rows(h, positions, n: int = POS_CHUNK):
    """Rows ``positions`` of ``h``, padded with row 0 to ``n`` rows."""
    pos = np.zeros(n, np.int32)
    pos[: len(positions)] = positions
    return h[jnp.asarray(pos)]
