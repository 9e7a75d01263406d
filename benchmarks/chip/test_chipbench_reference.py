"""The plain reference against the program's own forward pass at a
reduced size in float32, and its gap reading on known tokens."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import model  # noqa: E402


@pytest.fixture(scope="module", params=["qwen2.5-3b", "granite-8b"])
def small(request):
    """A reduced copy of each configuration (its program's CPU cut) with
    float32 weights drawn by the benchmark, the program's float32 forward
    pass, and the reference."""
    from repro.configs.runtime import RunConfig
    from repro.models.transformer import (
        ApplyCtx,
        abstract_model_params,
        forward_train,
    )

    config = json.loads((HERE / "configs" / f"{request.param}.json").read_text())
    config = cells.program_module(config).tiny(config)
    cfg = model.program_config(config)
    rcfg = RunConfig(param_dtype="float32", compute_dtype="float32", remat="none")
    params = model.make_weights(config, abstract_model_params(cfg, rcfg), 2**31 + 3)
    ctx = ApplyCtx(cfg, rcfg, None)

    def program_logits(tokens):
        with jax.default_matmul_precision("highest"):
            logits, _ = forward_train(ctx, params, {"tokens": jnp.asarray(tokens)})
        return np.asarray(logits)

    return config, params, program_logits, cells.reference_module(config)


def _reference_logits(ref, params, config, tokens):
    h = ref.hidden(params, config, tokens)
    head = params["embed"].T if config["tie_word_embeddings"] else params["head"]
    return np.asarray(jnp.einsum("rld,dv->rlv", h, head, precision="highest"))


def test_reference_matches_the_program_in_float32(small):
    config, params, program_logits, ref = small
    tokens = np.random.default_rng(0).integers(0, 512, (3, 40), dtype=np.int32)
    want = program_logits(tokens)
    got = _reference_logits(ref, params, config, tokens)
    assert np.abs(want).max() > 0.5  # logits of unit scale, not all zero
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(), rtol=0)


def test_served_gaps_of_greedy_and_of_altered_tokens(small):
    config, params, program_logits, ref = small
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, 12, dtype=np.int32)
    seq = np.zeros((1, 32), np.int32)  # one shape: causal, padding comes after
    seq[0, :12] = prompt
    for n in range(12, 18):  # greedy decoding by the program's full forward
        seq[0, n] = program_logits(seq)[0, n - 1].argmax()
    served = seq[0, 12:18]
    # the program's logits for tokens 0, 2 and 5, kept as the proxy would
    index = np.array([0, 2, 5])
    logits = program_logits(seq)[0, 11 + index]
    kept = [(index, logits)]
    gaps, errors = ref.compare(params, config, [(prompt, served)], 32, kept)[0]
    assert gaps.shape == (6,) and errors.shape == (3,)
    assert gaps.max() < 1e-4  # the program's own greedy tokens are the best
    assert errors.max() < 1e-3  # float32 program, float32 reference
    altered = served.copy()
    altered[3] = (altered[3] + 1) % 512
    worse, _ = ref.compare(params, config, [(prompt, altered)], 32, kept)[0]
    np.testing.assert_allclose(worse[:3], gaps[:3], atol=1e-5)
    assert worse[3] > 1e-2
    # logits kept for the wrong positions read as far off
    shifted = [(index, program_logits(seq)[0, 12 + index])]
    _, off = ref.compare(params, config, [(prompt, served)], 32, shifted)[0]
    assert off.max() > 100 * errors.max()


def test_control_reads_the_same_positions(small):
    """The int8 and fp8 controls read the same prompts and tokens and give
    one gap per served token and one logit error per kept position."""
    config, params, _, ref = small
    rng = np.random.default_rng(2)
    served = [(rng.integers(0, 512, 16, dtype=np.int32),
               rng.integers(0, 512, 16, dtype=np.int32)) for _ in range(2)]
    kept = [(np.array([0, 7]), np.zeros((2, 512), np.float32))] * 2
    for quant in ("int8", "fp8"):
        out = ref.compare(params, config, served, 32, kept, quant=quant)
        assert [(g.shape, e.shape) for g, e in out] == [((16,), (2,))] * 2
        assert all(np.all(g >= 0) and np.all(e >= 0) for g, e in out)
