"""The system under test, built from a configuration file and a seed.

The weights are the benchmark's own: drawn on the device from the run's
seed, in bfloat16, in one jitted call, laid out as the program's
parameter tree. The program serves them; the plain reference reads the
same arrays. Nothing of the program's own initialisation is used.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
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


def run_config():
    """The serving path's run configuration, with bfloat16 weights."""
    from repro.configs.runtime import serving_config

    return serving_config(param_dtype="bfloat16")


def seed_key(seed: int) -> np.ndarray:
    """Key data for any whole-number seed (wider than 32 bits included)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _leaf_init(path, leaf, key):
    name = str(getattr(path[-1], "key", path[-1]))
    shape, dt = leaf.shape, leaf.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name in _NORMS:
        return (1.0 + 0.1 * z).astype(dt)
    if name in _BIASES:
        return (0.5 * z).astype(dt)
    if name == _EMBED:
        return (z / np.sqrt(shape[-1])).astype(dt)
    return (z / np.sqrt(shape[-2])).astype(dt)


def make_weights(abstract_params, seed: int):
    """All weights from ``seed``, on the device, in one jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)

    @jax.jit
    def build(key_data):
        base = jax.random.wrap_key_data(key_data)
        return [
            _leaf_init(path, leaf, jax.random.fold_in(base, i))
            for i, (path, leaf) in enumerate(paths)
        ]

    return jax.tree_util.tree_unflatten(treedef, build(jnp.asarray(seed_key(seed))))


def build_engine(config: dict, mix: dict, seed: int):
    """The serving engine over seeded weights, sized for the mix."""
    from repro.models.transformer import ApplyCtx, abstract_model_params
    from repro.serving.engine import ServingEngine

    cfg = program_config(config)
    rcfg = run_config()
    params = make_weights(abstract_model_params(cfg, rcfg), seed)
    return ServingEngine(ApplyCtx(cfg, rcfg, None), params, int(mix["batch"]),
                         max_len(mix))


def max_len(mix: dict) -> int:
    """KV capacity: the longest prompt plus the longest output."""
    return int(max(mix["prompt_lens"])) + int(mix["output_lens"]["hi"])
