"""Per-layer mixer kinds (granite-4.0-h's Mamba-2 and attention layers in
one stack) leave every other configuration as it was, and the pieces the
stack rests on: its runs of one kind, the Mamba-2 conv tail, the
held-expert grouped products and equal-length serving groups."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.configs.registry import REGISTRY
from repro.configs.runtime import RunConfig
from repro.models import ssm as ssm_lib
from repro.models.transformer import (
    abstract_cache,
    abstract_model_params,
    init_model_params,
    kind_runs,
)
from repro.serving import Request, ServingRuntime

# sha256 (first 16 hex digits) of every parameter's and cache leaf's path,
# shape and dtype, in tree order, for each registry config at full size
# and at its reduced cut (bf16 parameters; cache of batch 4, capacity
# 64), as the tree before per-layer kinds built them
BEFORE = {
    ("deepseek-v2-236b", "full"): ("0e3453322e233493", "9fdda762b5fd13a9"),
    ("deepseek-v2-236b", "reduced"): ("d18e633ffcfed0b6", "2d020d258c123c4b"),
    ("granite-8b", "full"): ("46b629c374a36c9f", "4ed637e47921533d"),
    ("granite-8b", "reduced"): ("37d8be03dcc0e177", "2699b54325f26954"),
    ("hymba-1.5b", "full"): ("e6434f71b793e25d", "15787e82fe7bc4c8"),
    ("hymba-1.5b", "reduced"): ("b6666891dd48e7b2", "c78058e952fb9305"),
    ("internlm2-20b", "full"): ("6233a0e22c8ada56", "3ae2436d96e69fe9"),
    ("internlm2-20b", "reduced"): ("37d8be03dcc0e177", "2699b54325f26954"),
    ("mamba2-2.7b", "full"): ("b8b1a94f8cda2259", "9b943bad4c9340fe"),
    ("mamba2-2.7b", "reduced"): ("710082980e59e387", "52371cf13d36e8cb"),
    ("moonshot-v1-16b-a3b", "full"): ("224bcea90fa14aeb", "8a769ea3367f7cd0"),
    ("moonshot-v1-16b-a3b", "reduced"): ("0443f9ad720077f6", "3049a1a2b8c99097"),
    ("qwen2-vl-72b", "full"): ("b8487f89d6bfa681", "35faa49c112b50bc"),
    ("qwen2-vl-72b", "reduced"): ("9e2ec0502ad7e5ce", "2699b54325f26954"),
    ("qwen2.5-3b", "full"): ("b2083951e5b54811", "8d0c4148f59ef3ff"),
    ("qwen2.5-3b", "reduced"): ("9e2ec0502ad7e5ce", "2699b54325f26954"),
    ("qwen3-moe-235b-a22b", "full"): ("a8386f3fdf1263b2", "815c0ffcafca83e5"),
    ("qwen3-moe-235b-a22b", "reduced"): ("36a8a7b240d10a10", "2699b54325f26954"),
    ("whisper-medium", "full"): ("4c289510e42657b5", "81867a16563bc4ef"),
    ("whisper-medium", "reduced"): ("94213a524bcca15a", "e3737960851599ca"),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(f"{jax.tree_util.keystr(path)}:{a.shape}:{a.dtype};".encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,cut", sorted(BEFORE))
def test_every_registry_config_builds_the_trees_it_built_before(name, cut):
    cfg = REGISTRY[name] if cut == "full" else REGISTRY[name].reduced()
    assert cfg.layer_types is None
    params = abstract_model_params(cfg, RunConfig(param_dtype="bfloat16"))
    assert (_digest(params), _digest(abstract_cache(cfg, 4, 64))) == BEFORE[name, cut]


GRANITE_STAGE = ("mamba",) * 5 + ("attention",) + ("mamba",) * 9 + ("attention",) + (
    "mamba",) * 4


def _hybrid(kinds=GRANITE_STAGE, d=64) -> ModelConfig:
    return ModelConfig(
        name="hybrid", arch_type="hybrid", n_layers=len(kinds), d_model=d, n_heads=4,
        n_kv_heads=2, d_ff=0, vocab=128, rope_type="nope", layer_types=kinds,
        ssm=SSMConfig(d_state=16, headdim=16, chunk_size=8),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32, n_shared_experts=1,
                      n_routed=8, d_ff_shared=48))


def test_runs_of_one_kind_follow_the_published_pattern():
    """granite-4.0-h's first stage: five runs, each indexing its own
    kind's stack from where the last run of that kind ended."""
    assert kind_runs(_hybrid()) == [
        (0, 5, "mamba", 0), (5, 6, "attention", 0), (6, 15, "mamba", 5),
        (15, 16, "attention", 1), (16, 20, "mamba", 14)]


def test_parameters_stack_by_kind_and_the_count_agrees():
    cfg = _hybrid()
    params = abstract_model_params(cfg, RunConfig())
    layers = params["layers"]
    assert layers["mamba"]["in_proj"].shape[0] == 18
    assert layers["attn"]["wq"].shape[0] == 2
    assert layers["ffn"]["router"].shape == (20, 64, 8)  # every routed expert
    assert layers["ffn"]["we_gate"].shape == (20, 4, 64, 32)  # the held ones
    assert layers["ffn"]["ws_gate"].shape == (20, 64, 48)
    # the analytic count leaves out the final norm and three small Mamba
    # vectors (as it always has), and counts the router at its routed width
    left_out = params["final_norm"].size + sum(
        layers["mamba"][k].size for k in ("conv_b", "dt_bias", "norm_w"))
    assert cfg.n_params() == sum(a.size for a in jax.tree.leaves(params)) - left_out


def test_conv_tail_is_the_last_conv_inputs():
    """mamba2_forward returns the decode's conv state: the last d_conv - 1
    inputs of the conv, zeros before the first position."""
    cfg = ModelConfig(name="ssm", arch_type="ssm", n_layers=1, d_model=32, n_heads=0,
                      n_kv_heads=0, d_ff=0, vocab=64, rope_type="none",
                      ssm=SSMConfig(d_state=8, headdim=8, chunk_size=4))
    rcfg = RunConfig(param_dtype="float32", compute_dtype="float32")
    p = jax.tree.map(lambda a: a[0], init_model_params(
        jax.random.PRNGKey(0), cfg, rcfg)["layers"]["ssm"])
    di, n = cfg.ssm.inner(32), cfg.ssm.d_state
    for s in (10, 2):
        x = jax.random.normal(jax.random.PRNGKey(s), (2, s, 32))
        _, _, tail = ssm_lib.mamba2_forward(cfg, p, x, rcfg)
        xbc = (x @ p["in_proj"])[..., di:2 * di + 2 * n]
        want = jnp.pad(xbc, ((0, 0), (max(0, 3 - s), 0), (0, 0)))[:, -3:]
        np.testing.assert_allclose(tail, want, atol=1e-6)


def test_grouped_kernel_matches_ragged_dot():
    """The held experts' grouped products: the megablox kernel (interpret
    mode here) and ``lax.ragged_dot`` agree on every row of a group; an
    empty group and the rows past the last group are skipped."""
    from repro.models.moe import _grouped

    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 256, 384)), jnp.float32)
    sizes = jnp.asarray([30, 0, 50, 20], jnp.int32)
    kernel = _grouped(rows, w, sizes, 128, pallas=True)
    plain = _grouped(rows, w, sizes, 128, pallas=False)
    np.testing.assert_allclose(np.asarray(kernel)[:100], np.asarray(plain)[:100],
                               rtol=1e-5, atol=1e-4)
    want = np.concatenate([np.asarray(rows[:30] @ w[0]), np.asarray(rows[30:80] @ w[2]),
                           np.asarray(rows[80:100] @ w[3])])
    np.testing.assert_allclose(np.asarray(plain)[:100], want, rtol=1e-5, atol=1e-4)


class _Recording:
    """A serving engine that records the prompts of each prefill."""

    def __init__(self, batch):
        self.batch, self.max_len, self.prompts = batch, 64, []

    def prefill(self, tokens, extras=None):
        self.prompts.append(np.asarray(tokens))
        b = tokens.shape[0]
        return {"length": tokens.shape[1]}, jnp.zeros((b, 1, 8))

    def decode(self, cache, tokens):
        return {"length": cache["length"] + 1}, jnp.zeros((tokens.shape[0], 1, 8))


def test_groups_hold_prompts_of_one_length():
    """A group is prefilled from prompts of one length, so no padding
    enters a row's recurrence; a short group is filled with rows of its
    own, not with positions."""
    eng = _Recording(batch=3)
    rt = ServingRuntime(eng, batch_size=3, concurrency=2)
    rng = np.random.default_rng(0)
    lengths = [8, 12, 8, 12, 8, 5]
    for i, n in enumerate(lengths):
        rt.submit(Request(i, rng.integers(1, 100, n, dtype=np.int32), 2))
    while rt.step():
        pass
    assert sorted(p.shape[1] for p in eng.prompts) == [5, 8, 12]
    for p in eng.prompts:
        real = p[p.any(axis=1)]
        assert (real != 0).all()  # every real row is a whole prompt, unpadded
    assert sum(len(p[p.any(axis=1)]) for p in eng.prompts) == len(lengths)
