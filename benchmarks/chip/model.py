"""The system under test, built from a configuration file and a seed.

The weights are the benchmark's own: drawn on the device from the run's
seed, in bfloat16, in one jitted call, laid out as the program's
parameter tree. The program serves them; the plain reference reads the
same arrays. Nothing of the program's own initialisation is used.

What differs by model family (the ``ModelConfig`` a configuration file
maps to, the scale of each weight) comes from the configuration's
program module, ``programs/<name>.py`` (``cells.program_module``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import cells


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return cells.program_module(config).program_config(config)


def run_config(config: dict, **overrides):
    """The serving path's run configuration, with bfloat16 weights and
    what the configuration's program asks for."""
    from repro.configs.runtime import serving_config

    program = cells.program_module(config)
    return serving_config(param_dtype="bfloat16",
                          **{**getattr(program, "RUN_CONFIG", {}), **overrides})


def seed_key(seed: int) -> np.ndarray:
    """Key data for any whole-number seed (wider than 32 bits included)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _leaf_init(path, leaf, key, rule):
    """One leaf from its own key: a standard-normal draw that the
    program's ``leaf_init`` scales, or 1/sqrt(fan_in) where it declines."""
    name = str(getattr(path[-1], "key", path[-1]))
    shape, dt = leaf.shape, leaf.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    value = rule(name, shape, z)
    if value is None:
        value = z / np.sqrt(shape[-2])
    return value.astype(dt)


def weight_builder(config: dict, abstract_params):
    """The function from key data to every leaf of ``abstract_params``,
    in tree order, each drawn by the configuration's program."""
    paths, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    rule = cells.program_module(config).leaf_init

    def build(key_data):
        base = jax.random.wrap_key_data(key_data)
        return [
            _leaf_init(path, leaf, jax.random.fold_in(base, i), rule)
            for i, (path, leaf) in enumerate(paths)
        ]

    return build


def make_weights(config: dict, abstract_params, seed: int):
    """All weights from ``seed``, on the device, in one jitted call."""
    treedef = jax.tree_util.tree_structure(abstract_params)
    build = jax.jit(weight_builder(config, abstract_params))
    return jax.tree_util.tree_unflatten(treedef, build(jnp.asarray(seed_key(seed))))


def build_engine(config: dict, mix: dict, seed: int):
    """The serving engine over seeded weights, sized for the mix."""
    from repro.models.transformer import ApplyCtx, abstract_model_params
    from repro.serving.engine import ServingEngine

    cfg = program_config(config)
    rcfg = run_config(config)
    params = make_weights(config, abstract_model_params(cfg, rcfg), seed)
    return ServingEngine(ApplyCtx(cfg, rcfg, None), params, int(mix["batch"]),
                         max_len(mix))


def max_len(mix: dict) -> int:
    """KV capacity: the longest prompt plus the longest output."""
    return int(max(mix["prompt_lens"])) + int(mix["output_lens"]["hi"])
