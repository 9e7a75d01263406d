"""The program for granite-4.0-h (``granitemoehybrid``): Mamba-2 and NoPE
attention layers in a published pattern, an expert layer after every
mixer of which this chip holds a share, and the muP multipliers. The
configuration file's keys as the program's ``ModelConfig``, the scale of
each weight the benchmark draws, the cut the CPU tests use, and the
operations and bytes that this configuration's per-layer metrics count.

Scales: a trained granite's residual multiplier (0.22) scales down
branch outputs larger than the stream's own updates, so each mixer's and
expert layer's output matrix (``out_proj``, ``wo``, ``we_down``,
``ws_down``) is drawn at 1/(0.22·sqrt(fan_in)) and every layer adds a
unit-scale update to the residual stream, as in the dense cells; every
other matrix takes 1/sqrt(fan_in). The embedding is drawn at 1/sqrt(d):
through the tied head (÷16) the logits have a standard deviation of
1/16 at any width, and the embedding (×12, RMS 12/sqrt(d)) stays a small
part of the stream. Drawn at 1/12, it held a third of the stream after
20 layers and greedy decoding repeated each row's input token. The
Mamba-2 leaves follow Mamba-2's own initialisation: A in [-16, -1], the
step size log-uniform in [1e-3, 0.1], D near 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2  # bytes of every weight and cache entry, as served
DT_MIN, DT_MAX = 1e-3, 0.1  # range of the initial step size, as in Mamba-2

_NORMS = ("ln1", "ln2", "final_norm", "norm_w")  # 1 + 0.1·N(0, 1)
_OUTPUTS = ("out_proj", "wo", "we_down", "ws_down")  # join the residual × 0.22
_RESIDUAL = 0.22  # the published residual multiplier


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

    d = int(config["hidden_size"])
    held = [int(e) for e in config["held_experts"]]
    n_heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    supported = {
        "held experts, consecutive ids, num_local_experts of them":
            held == list(range(held[0], held[0] + len(held)))
            and len(held) == int(config["num_local_experts"]),
        "mamba_n_heads × mamba_d_head = mamba_expand × hidden_size":
            n_heads * d_head == int(config["mamba_expand"]) * d,
        "one group, a conv bias, no projection bias":
            int(config["mamba_n_groups"]) == 1 and bool(config["mamba_conv_bias"])
            and not config["mamba_proj_bias"] and not config["attention_bias"],
        "no positional encoding": config["position_embedding_type"] == "nope",
    }
    for what, ok in supported.items():
        if not ok:
            raise ValueError(f"{config['model_type']}: the program needs {what}")
    shared = int(config["shared_intermediate_size"])
    return ModelConfig(
        name=config["model_type"],
        arch_type="hybrid",
        n_layers=int(config["num_hidden_layers"]),
        d_model=d,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=0,
        vocab=int(config["vocab_size"]),
        rope_type="nope",
        attention_multiplier=float(config["attention_multiplier"]),
        layer_types=tuple(config["layer_types"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        moe=MoEConfig(
            n_experts=len(held),
            top_k=int(config["num_experts_per_tok"]),
            d_ff_expert=int(config["intermediate_size"]),
            n_shared_experts=1 if shared else 0,
            n_routed=int(config["num_routed_experts"]),
            first_held=held[0],
            d_ff_shared=shared or None,
        ),
        ssm=SSMConfig(
            d_state=int(config["mamba_d_state"]),
            headdim=d_head,
            expand=int(config["mamba_expand"]),
            chunk_size=int(config["mamba_chunk_size"]),
            d_conv=int(config["mamba_d_conv"]),
            d_inner=n_heads * d_head,
        ),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        source=config["source"],
    )


def leaf_init(name: str, shape, z):
    """The leaf ``name`` from its standard-normal draw ``z`` (float32), or
    None for the default 1/sqrt(fan_in) scale."""
    u = jax.scipy.stats.norm.cdf(z)  # uniform on (0, 1)
    if name == "A_log":  # A = -exp(A_log) in [-16, -1]
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":  # softplus(dt_bias) log-uniform in [DT_MIN, DT_MAX]
        dt = jnp.exp(np.log(DT_MIN) + u * (np.log(DT_MAX) - np.log(DT_MIN)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "D" or name in _NORMS:
        return 1.0 + 0.1 * z
    if name == "conv_b":
        return 0.1 * z
    if name == "embed":
        return z / np.sqrt(shape[-1])
    if name in _OUTPUTS:
        return z / (_RESIDUAL * np.sqrt(shape[-2]))
    return None


def tiny(config: dict) -> dict:
    """The configuration cut to what a CPU test holds: both mixer kinds,
    two attention layers as the chip's stage has, and a router wider than
    the experts held."""
    kinds = ["mamba", "attention", "mamba", "mamba"] * 2
    return dict(
        config, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        num_hidden_layers=len(kinds), layer_types=kinds,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
        intermediate_size=32, shared_intermediate_size=48, num_local_experts=8,
        num_routed_experts=16, held_experts=list(range(8)), num_experts_per_tok=4,
        vocab_size=512,
    )


# ---------------------------------------------------------------------------
# Operations and bytes, from the configuration's shapes (bfloat16)


def _sizes(config: dict) -> dict:
    d = int(config["hidden_size"])
    nh, hd = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    n = int(config["mamba_d_state"])
    kinds = list(config["layer_types"])
    return {
        "d": d, "di": nh * hd, "nh": nh, "hd": hd, "n": n,
        "conv": nh * hd + 2 * n, "k": int(config["mamba_d_conv"]),
        "hq": int(config["num_attention_heads"]),
        "hkv": int(config["num_key_value_heads"]),
        "ahd": d // int(config["num_attention_heads"]),
        "n_mamba": kinds.count("mamba"), "n_attn": kinds.count("attention"),
        "layers": len(kinds), "vocab": int(config["vocab_size"]),
        "held": int(config["num_local_experts"]),
        "routed": int(config["num_routed_experts"]),
        "ff": int(config["intermediate_size"]),
        "shared": int(config["shared_intermediate_size"]),
    }


def param_count(config: dict) -> int:
    """Every parameter of the program (the tied embedding once)."""
    s = _sizes(config)
    d, di, nh, conv = s["d"], s["di"], s["nh"], s["conv"]
    mamba = (d * (2 * di + 2 * s["n"] + nh) + s["k"] * conv + conv + 3 * nh + di
             + di * d)
    attn = 2 * d * s["hq"] * s["ahd"] + 2 * d * s["hkv"] * s["ahd"]
    # router, held experts, shared expert, norms
    ffn = d * s["routed"] + 3 * d * (s["held"] * s["ff"] + s["shared"]) + 2 * d
    return (s["n_mamba"] * mamba + s["n_attn"] * attn + s["layers"] * ffn
            + s["vocab"] * d + d)


def state_bytes(config: dict, rows: int) -> float:
    """SSM and conv state of ``rows`` sequences, every Mamba layer."""
    s = _sizes(config)
    per_row = s["nh"] * s["hd"] * s["n"] + (s["k"] - 1) * s["conv"]
    return BF16 * s["n_mamba"] * rows * per_row


def decode_bytes(config: dict, rows: int, context: int) -> float:
    """The least one decode step of ``rows`` tokens moves: every weight
    once (the tied head once, all held experts), every row's SSM and conv
    state read and written, and the attention layers' live keys and
    values over ``context`` positions."""
    s = _sizes(config)
    kv = BF16 * 2.0 * s["n_attn"] * rows * context * s["hkv"] * s["ahd"]
    return BF16 * param_count(config) + 2.0 * state_bytes(config, rows) + kv

