"""Plain float32 reference of granite-4.0-h (``granitemoehybrid``).

Written from the published description (the model's config.json and the
GraniteMoeHybrid decoder) in straightforward ``jax.numpy``; every matrix
product at ``Precision.HIGHEST``, so float32 on a TPU too. It imports
nothing of the program. Per layer:

    h = h + r · mixer(rms(h))            mixer: Mamba-2 or attention
    h = h + r · (experts(x) + shared(x)),  x = rms(h)

with r the residual multiplier; the embedding is multiplied by the
embedding multiplier and the logits (tied head) divided by the logits
scaling.

- Mamba-2: in_proj to (z, x, B, C, dt), a depthwise causal conv of
  width 4 with its bias over (x, B, C), SiLU, then the recurrence one
  position at a time: S = exp(dt·A)·S + (dt·x) ⊗ B, y = S·C + D·x, one
  group; the output through the gated RMSNorm rms(y · silu(z)) and
  out_proj.
- Attention: causal, no positional encoding (NoPE), softmax scale the
  attention multiplier (1/128), grouped query heads.
- Experts: the router scores every routed expert (72), takes the top k
  (10) and a softmax over the k chosen logits; each expert a SwiGLU of
  the expert width. The shared expert is a SwiGLU of its own width.

Departure from the published model, as the benchmark's configuration
states it: the expert layer sums only the experts held here
(``held_experts``, 9 of 72, one rank of eight under expert parallelism),
each with its gate normalised over all k chosen experts; the absent
experts' part is left out here as in the program. The shared expert is
counted once.

It reads the benchmark's weights by the serving program's names:
``embed`` (V, d), ``final_norm``, and under ``layers``: ``ln1``, ``ln2``
and ``ffn/{router, we_gate, we_up, we_down, ws_gate, ws_up, ws_down}``
stacked over every layer, ``mamba/{in_proj, conv_w, conv_b, dt_bias,
A_log, D, norm_w, out_proj}`` over the Mamba layers and
``attn/{wq, wk, wv, wo}`` over the attention layers, each in layer order.

It runs layer by layer over blocks of rows, so that the model fits
beside its weights, and returns what ``references/dense_gqa.py``
returns: for each served token how far the reference's logit of it lies
below the reference's best, and at each kept position the largest
difference between the program's logits and the reference's.

``quant`` gives the control: the same computation with every matrix of
the mixers, the experts and the head rounded to a lower precision first
(``int8``; ``fp8``: float8_e4m3fn, one scale per output channel), and
the SSM state carried from one position to the next rounded the same
way (one scale per row and head), as a program that kept its state in
that precision would. The router is left in full precision, as a
lower-precision deployment keeps it.
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
MAMBA = "mamba"


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _weight(w, quant):
    """A matrix in float32, optionally rounded through ``quant`` first
    (one scale per output column, the last axis)."""
    return _rounded(w.astype(jnp.float32), quant, (-2,))


def _rounded(w, quant, axes):
    """``w`` rounded through ``quant``, one scale over ``axes``."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown quantisation {quant!r}")


def _f32(w):
    return w.astype(jnp.float32)


def _swiglu(x, wg, wu, wd, quant):
    g = _mm("rld,df->rlf", x, _weight(wg, quant))
    u = _mm("rld,df->rlf", x, _weight(wu, quant))
    return _mm("rlf,fd->rld", jax.nn.silu(g) * u, _weight(wd, quant))


def _mamba(p, x, arch, quant):
    """The Mamba-2 mixer over normed rows x (R, L, d)."""
    nh, hd, n, eps = arch["nh"], arch["hd"], arch["n"], arch["eps"]
    r, length, _ = x.shape
    di = nh * hd
    proj = _mm("rld,de->rle", x, _weight(p["in_proj"], quant))
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], proj[..., 2 * di + 2 * n:]
    w = _f32(p["conv_w"])  # (K, channels): depthwise
    k = w.shape[0]
    pad = jnp.concatenate([jnp.zeros((r, k - 1, xbc.shape[-1])), xbc], axis=1)
    conv = sum(pad[:, i:i + length] * w[i] for i in range(k)) + _f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(r, length, nh, hd)
    b, c = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))  # (R, L, nh)
    a = -jnp.exp(_f32(p["A_log"]))

    def step(state, t):  # state (R, nh, hd, n)
        x_t, b_t, c_t, dt_t = t
        state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                 + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        state = _rounded(state, quant, (-2, -1))
        return state, _mm("rhpn,rn->rhp", state, c_t)

    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0),
           jnp.moveaxis(dt, 1, 0))
    _, y = jax.lax.scan(step, jnp.zeros((r, nh, hd, n)), seq)
    y = jnp.moveaxis(y, 0, 1) + xs * _f32(p["D"])[:, None]
    y = _rms(y.reshape(r, length, di) * jax.nn.silu(z), _f32(p["norm_w"]), eps)
    return _mm("rle,ed->rld", y, _weight(p["out_proj"], quant))


def _attention(p, x, arch, quant):
    """Causal NoPE attention over normed rows x (R, L, d)."""
    hq, hkv, scale = arch["hq"], arch["hkv"], arch["scale"]
    r, length, d = x.shape
    hd = d // hq
    q = _mm("rld,de->rle", x, _weight(p["wq"], quant)).reshape(r, length, hq, hd)
    k = _mm("rld,de->rle", x, _weight(p["wk"], quant)).reshape(r, length, hkv, hd)
    v = _mm("rld,de->rle", x, _weight(p["wv"], quant)).reshape(r, length, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)  # head j reads kv head j // (hq // hkv)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = _mm("rqhd,rkhd->rhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
    o = _mm("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1), v)
    return _mm("rle,ed->rld", o.reshape(r, length, hq * hd), _weight(p["wo"], quant))


def _experts(p, x, arch, quant):
    """The held experts' gated sum and the shared expert over x (R, L, d)."""
    logits = _mm("rld,de->rle", x, _f32(p["router"]))  # every routed expert
    top, ids = jax.lax.top_k(logits, arch["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    y = _swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"], quant)
    for j, expert in enumerate(arch["held"]):
        gate = jnp.sum(jnp.where(ids == expert, gates, 0.0), axis=-1)  # (R, L)
        out = _swiglu(x, p["we_gate"][j], p["we_up"][j], p["we_down"][j], quant)
        y = y + gate[..., None] * out
    return y


@functools.partial(jax.jit, static_argnames=("kind", "arch_key", "quant"))
def _layer(layers, i, j, h, kind, arch_key, quant):
    """Layer ``i`` (its ``j``-th of ``kind``) over the rows h (R, L, d)."""
    arch = dict(arch_key)
    take = functools.partial(jax.tree.map, lambda a: a[j])
    x = _rms(h, _f32(layers["ln1"][i]), arch["eps"])
    if kind == MAMBA:
        out = _mamba(take(layers["mamba"]), x, arch, quant)
    else:
        out = _attention(take(layers["attn"]), x, arch, quant)
    h = h + arch["residual"] * out
    x = _rms(h, _f32(layers["ln2"][i]), arch["eps"])
    ffn = jax.tree.map(lambda a: a[i], layers["ffn"])
    return h + arch["residual"] * _experts(ffn, x, arch, quant)


def _arch(config: dict) -> Tuple:
    """The numbers the layers take, as a hashable key."""
    return tuple(sorted({
        "nh": int(config["mamba_n_heads"]), "hd": int(config["mamba_d_head"]),
        "n": int(config["mamba_d_state"]), "eps": float(config["rms_norm_eps"]),
        "hq": int(config["num_attention_heads"]),
        "hkv": int(config["num_key_value_heads"]),
        "scale": float(config["attention_multiplier"]),
        "top_k": int(config["num_experts_per_tok"]),
        "held": tuple(int(e) for e in config["held_experts"]),
        "residual": float(config["residual_multiplier"]),
    }.items()))


@functools.partial(jax.jit, static_argnames=("quant", "mult"))
def _embed(table, tokens, quant, mult):
    # an embedding row is a column of the tied head: round it the same way
    w = _weight(table.T, quant).T if quant else table.astype(jnp.float32)
    return w[tokens] * mult


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _final(h, norm, eps, scaling):
    # the logits' division, applied to the hidden state before the head
    return _rms(h, norm.astype(jnp.float32), eps) / scaling


def hidden(weights, config: dict, tokens: np.ndarray, quant=None) -> jax.Array:
    """Final-norm hidden states (R, L, d) of the rows of ``tokens``,
    divided by the logits scaling: times the head, they are the logits."""
    arch = _arch(config)
    h = _embed(weights["embed"], jnp.asarray(tokens), quant,
               float(config["embedding_multiplier"]))
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(config["layer_types"]):
        h = _layer(weights["layers"], jnp.int32(i), jnp.int32(seen[kind]), h, kind,
                   arch, quant)
        seen[kind] += 1
    return _final(h, weights["final_norm"], float(config["rms_norm_eps"]),
                  float(config["logits_scaling"]))


def rows_per_block(config: dict, length: int, budget_bytes: float = 1.0e9) -> int:
    """Rows whose float32 attention scores, or in_proj output, fit in
    ``budget_bytes``."""
    d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    width = 2 * int(config["mamba_n_heads"]) * int(config["mamba_d_head"]) + d
    per_row = 4.0 * max(hq * length * length, length * width)
    return max(1, int(budget_bytes // per_row))


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
    ref = _mm("kd,dv->kv", ref_h, head.astype(jnp.float32))
    return jnp.max(jnp.abs(logits.astype(jnp.float32) - ref), axis=-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _errors_of_control(head, ref_h, other_h, quant):
    ref = _mm("kd,dv->kv", ref_h, head.astype(jnp.float32))
    other = _mm("kd,dv->kv", other_h, _weight(head, quant))
    return jnp.max(jnp.abs(other - ref), axis=-1)


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
    compiled shape); padding sits after every real position and, causal
    in every mixer, changes none of them. With ``quant`` the control takes
    the program's place: the tokens judged are its own first choices, and
    the logits compared its own, at the same positions.
    """
    head = weights["embed"].T  # tied
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
