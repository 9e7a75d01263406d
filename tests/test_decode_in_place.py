"""The engine's decode updates its KV cache in place: the cache is donated
to ``jit_serve_step`` and carried through the layer scan. It must give the
logits of the undonated step, for every cache kind the registry has; a
handle given to ``decode`` must stay usable (a stale handle decodes as it
did before it was given); and every decode must consume its storage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import REGISTRY
from repro.configs.runtime import RunConfig
from repro.models import ApplyCtx, init_model_params
from repro.serving import Request, ServingEngine, ServingRuntime
from repro.serving.engine import make_serve_step

RCFG = RunConfig(remat="none", moe_impl="dense")
B, PROMPT, CAPACITY, N_DEC = 2, 8, 16, 4

# one tiny config per cache kind: dense GQA K/V, MLA latent (and a dense
# prologue stack), hybrid attention + SSM state over sliding-window
# segments, pure SSM state, encoder-decoder cross K/V
KINDS = ["qwen2.5-3b", "deepseek-v2-236b", "hymba-1.5b", "mamba2-2.7b",
         "whisper-medium"]
# the stale-handle rule holds where every cache leaf is a ring of slots
RING_KINDS = ["qwen2.5-3b", "deepseek-v2-236b", "whisper-medium"]


def _engine(name):
    cfg = REGISTRY[name].reduced()
    ctx = ApplyCtx(cfg, RCFG, None)
    params = init_model_params(jax.random.PRNGKey(0), cfg, RCFG)
    return ServingEngine(ctx, params, batch_size=B, max_len=CAPACITY)


def _prefill(eng):
    cfg = eng.ctx.cfg
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab)
    )
    extras = None
    if cfg.is_encoder_decoder:
        extras = {"enc_feats": np.full(
            (B, cfg.encoder_seq_len, cfg.d_model), 0.02, np.float32)}
    cache, logits = eng.prefill(tokens, extras)
    return cache, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)


@pytest.mark.parametrize("name", KINDS)
def test_engine_decode_matches_undonated_step(name):
    eng = _engine(name)
    cache, tok = _prefill(eng)
    ref = jax.tree.map(jnp.copy, cache)
    step = jax.jit(make_serve_step(eng.ctx))
    for _ in range(N_DEC):
        cache, logits = eng.decode(cache, tok)
        ref, ref_logits = step(eng.params, ref, tok)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    assert int(cache["length"]) == int(ref["length"]) == PROMPT + N_DEC
    assert eng.kv_in_place == eng.decodes == N_DEC


@pytest.mark.parametrize("name", RING_KINDS)
def test_stale_handle_decodes_as_before(name):
    """Decoding one handle twice gives the same logits, and the handle the
    first decode returned goes on as if the second never happened."""
    eng = _engine(name)
    cache, tok = _prefill(eng)
    ref = jax.tree.map(jnp.copy, cache)
    step = jax.jit(make_serve_step(eng.ctx))
    fresh, first = eng.decode(cache, tok)
    _, again = eng.decode(cache, tok)  # ``cache`` is stale now
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    assert int(cache["length"]) == PROMPT
    ref, _ = step(eng.params, ref, tok)
    _, logits = eng.decode(fresh, tok)
    _, ref_logits = step(eng.params, ref, tok)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert eng.kv_in_place == eng.decodes == 3


def test_runtime_decodes_all_in_place():
    eng = _engine("qwen2.5-3b")
    rt = ServingRuntime(eng, concurrency=2)
    rng = np.random.default_rng(0)
    for rid in range(5):
        rt.submit(Request(rid, rng.integers(0, 512, PROMPT, dtype=np.int32), 3))
    rt.drain()
    assert len(rt.done) == 5
    assert eng.decodes > 0 and eng.kv_in_place == eng.decodes
