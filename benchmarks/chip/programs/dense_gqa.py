"""The program for a dense GQA decoder (the Llama and Qwen2 families):
the configuration file's keys as the program's ``ModelConfig``, the
scale of each weight the benchmark draws, and the cut the CPU tests use.

The default program: a configuration file without a ``"program"`` key
is served through this one.
"""
from __future__ import annotations

import numpy as np

# standard deviation of each kind of leaf, by the leaf's name; matrices
# not listed get 1/sqrt(fan_in), which keeps every projection's output
# near unit scale at any width
_NORMS = ("ln1", "ln2", "final_norm")  # 1 + 0.1·N(0, 1)
_BIASES = ("bq", "bk", "bv")  # 0.5·N(0, 1): large enough to matter
_EMBED = "embed"  # 1/sqrt(d): unit-scale logits through a tied head


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config.get("model_type", "dense"),
        arch_type="dense",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        qkv_bias=bool(config["qkv_bias"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        source=config["source"],
    )


def leaf_init(name: str, shape, z):
    """The leaf ``name`` from its standard-normal draw ``z`` (float32), or
    None for the default 1/sqrt(fan_in) scale."""
    if name in _NORMS:
        return 1.0 + 0.1 * z
    if name in _BIASES:
        return 0.5 * z
    if name == _EMBED:
        return z / np.sqrt(shape[-1])
    return None


def tiny(config: dict) -> dict:
    """The configuration cut to what a CPU test holds."""
    return dict(config, hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
