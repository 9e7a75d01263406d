"""granite-4.0-h-small at its CPU cut against its plain float32 reference,
on the benchmark's seeded weights: prefill and decode through the
serving engine's in-place cache, the held-expert layer's shares, the
cache by layer kind, NoPE and the attention scale, and the readers of
the configuration's own per-layer metrics on synthetic traces."""
import dataclasses
import functools
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import model  # noqa: E402
import reading  # noqa: E402
import xtrace  # noqa: E402

CELL = "granite-4.0-h-small.offline_b32"
SEED = 2**31 + 5


def _config() -> dict:
    return json.loads((HERE / "configs" / "granite-4.0-h-small.json").read_text())


@functools.lru_cache(maxsize=None)
def _tiny():
    """The CPU cut, its program's ``ModelConfig``, float32 weights drawn
    by the benchmark, and the plain reference."""
    from repro.configs.runtime import RunConfig
    from repro.models.transformer import abstract_model_params

    # the first four layers of the program's CPU cut: over eight, a near
    # tie in one router flips an expert between the program (its state
    # rounded to bfloat16 in the cache) and the reference at one decode
    # step of these tokens, a jump of 5e-3 that no tolerance separates
    # from a lower precision
    config = cells.program_module(_config()).tiny(_config())
    config = dict(config, num_hidden_layers=4, layer_types=config["layer_types"][:4])
    cfg = model.program_config(config)
    rcfg = RunConfig(param_dtype="float32", compute_dtype="float32", remat="none")
    params = model.make_weights(config, abstract_model_params(cfg, rcfg), SEED)
    return config, cfg, rcfg, params, cells.reference_module(config)


def _reference_logits(ref, params, config, tokens):
    h = ref.hidden(params, config, tokens)
    return np.asarray(jnp.einsum("rld,vd->rlv", h, params["embed"], precision="highest"))


def _serve(params, tokens, prompt, state_fault=None):
    """Prefill ``tokens[:, :prompt]`` and decode the rest, one token a step,
    through ``ServingEngine`` (cache donated, updated in place): the
    logits each step gave for the next position. ``state_fault`` rewrites
    the carried SSM state between steps."""
    from repro.models.transformer import ApplyCtx
    from repro.serving.engine import ServingEngine

    config, cfg, rcfg, _, _ = _tiny()
    eng = ServingEngine(ApplyCtx(cfg, rcfg, None), params, tokens.shape[0],
                        tokens.shape[1])
    cache, logits = eng.prefill(tokens[:, :prompt])
    out = [np.asarray(logits)[:, -1]]
    for j in range(prompt, tokens.shape[1]):
        if state_fault is not None:
            cache["main"]["ssm"] = state_fault(cache["main"]["ssm"])
        cache, logits = eng.decode(cache, jnp.asarray(tokens[:, j:j + 1]))
        out.append(np.asarray(logits)[:, -1])
    assert eng.kv_in_place == eng.decodes == tokens.shape[1] - prompt
    return np.stack(out, axis=1)


PROMPT, DECODES = 20, 10
# prefill: a float32 program against a float32 reference differ by the
# order of summation alone (below 1e-6 of logits up to 0.4); the held
# experts' weights rounded to bfloat16 read 2.5e-4
PREFILL_ATOL = 1e-5
# decode: the cache holds K/V, SSM and conv state in bfloat16, as served,
# one rounding (2**-9) a step; ten steps read at most 5.6e-4, and the SSM
# state kept in float8 instead reads 2.8e-3
DECODE_ATOL = 1e-3


def _tokens():
    config = _tiny()[0]
    return np.random.default_rng(0).integers(
        0, config["vocab_size"], (3, PROMPT + DECODES)).astype(np.int32)


def test_prefill_and_decode_match_the_reference():
    config, _, _, params, ref = _tiny()
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        want = _reference_logits(ref, params, config, tokens)[:, PROMPT - 1:]
        got = _serve(params, tokens, PROMPT)
    assert np.abs(want).max() > 0.1  # logits of std 1/16 (÷16), not zero
    assert np.abs(got[:, 0] - want[:, 0]).max() <= PREFILL_ATOL
    assert np.abs(got[:, 1:] - want[:, 1:]).max() <= DECODE_ATOL


def test_lower_precision_fails_the_tolerances():
    """The tolerances catch the held experts computed in bfloat16 (prefill)
    and the SSM state carried in float8 (decode)."""
    config, _, _, params, ref = _tiny()
    tokens = _tokens()

    def bf16_experts(path, a):
        name = str(getattr(path[-1], "key", ""))
        return a.astype(jnp.bfloat16).astype(a.dtype) if name.startswith("we_") else a

    def fp8_state(s):
        return s.astype(jnp.float8_e4m3fn).astype(s.dtype)

    with jax.default_matmul_precision("highest"):
        want = _reference_logits(ref, params, config, tokens)[:, PROMPT - 1:]
        rounded = jax.tree_util.tree_map_with_path(bf16_experts, params)
        got = _serve(rounded, tokens, PROMPT)
        assert np.abs(got[:, 0] - want[:, 0]).max() > PREFILL_ATOL
        got = _serve(params, tokens, PROMPT, state_fault=fp8_state)
        assert np.abs(got[:, 1:] - want[:, 1:]).max() > DECODE_ATOL


# ---------------------------------------------------------------------------
# The held-expert layer


def _moe_config(routed: int, held: int, top_k: int, first: int = 0):
    from repro.configs.base import ModelConfig, MoEConfig

    return ModelConfig(
        name="moe", arch_type="moe", n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=0, vocab=64,
        moe=MoEConfig(n_experts=held, top_k=top_k, d_ff_expert=32, n_shared_experts=1,
                      n_routed=routed, first_held=first, d_ff_shared=48))


def _moe_weights(routed: int, seed: int = 0) -> dict:
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = jax.random.normal
    return {
        "router": n(k[0], (64, routed)) / 8.0,
        "we_gate": n(k[1], (routed, 64, 32)) / 8.0,
        "we_up": n(k[2], (routed, 64, 32)) / 8.0,
        "we_down": n(k[3], (routed, 32, 64)) / np.sqrt(32),
        "ws_gate": n(k[4], (64, 48)) / 8.0,
        "ws_up": n(k[5], (64, 48)) / 8.0,
        "ws_down": n(k[6], (48, 64)) / np.sqrt(48),
    }


def _held(cfg, p, x):
    from repro.configs.runtime import RunConfig
    from repro.models.moe import moe_ffn_held

    return moe_ffn_held(cfg, RunConfig(), p, x)[0]


@pytest.mark.parametrize("impl", ["dense", "auto"])
def test_a_layer_holding_a_share_takes_the_held_path(impl):
    """The router scoring more experts than the layer holds selects the
    held path whatever ``moe_impl`` asks for: the compute-all-experts
    path cannot run a share."""
    from repro.configs.runtime import RunConfig
    from repro.models.moe import moe_ffn

    cfg = _moe_config(16, 8, 4)
    p = {k: v[:8] if k.startswith("we_") else v for k, v in _moe_weights(16).items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 64))
    got, _ = moe_ffn(cfg, RunConfig(moe_impl=impl), None, p, x)
    np.testing.assert_array_equal(got, _held(cfg, p, x))


@pytest.mark.parametrize("routed,held,top_k", [(16, 8, 4), (72, 9, 10)])
def test_the_shares_add_up_to_the_uncut_layer(routed, held, top_k):
    """Every rank's held-expert part, with the shared expert counted once,
    is the layer that holds every expert (16 over 2 ranks as at the CPU
    cut; 72 over 8 ranks of 9 as published), and that layer is the
    compute-all-experts one."""
    from repro.models.moe import _shared, moe_ffn_dense

    p = _moe_weights(routed)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 40, 64))
    with jax.default_matmul_precision("highest"):
        shared = _shared(p, x.reshape(-1, 64)).reshape(x.shape)
        parts = []
        for first in range(0, routed, held):
            cfg = _moe_config(routed, held, top_k, first)
            share = {k: v[first:first + held] if k.startswith("we_") else v
                     for k, v in p.items()}
            parts.append(_held(cfg, share, x) - shared)
        whole = _held(_moe_config(routed, routed, top_k), p, x)
        dense, _ = moe_ffn_dense(_moe_config(routed, routed, top_k), p, x)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5, rtol=0)
    np.testing.assert_allclose(whole, dense, atol=2e-5, rtol=0)


def test_a_router_to_one_held_expert_drops_no_token():
    """Every token routed to held expert 3 (and three others): each gets
    that expert's part, as the reference's dense loop over the held
    experts computes it."""
    config, _, _, _, ref = _tiny()
    cfg = _moe_config(16, 8, 4)
    p = _moe_weights(16)
    p["router"] = p["router"].at[:, 3].set(0.0).at[0, 3].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 64))
    x = x.at[..., 0].set(1.0)  # the router's expert-3 logit is 100 for every token
    arch = dict(ref._arch(dict(config, num_experts_per_tok=4)))
    with jax.default_matmul_precision("highest"):
        logits = x.reshape(-1, 64) @ p["router"]
        assert (jnp.argmax(logits, -1) == 3).all()
        got = _held(cfg, {k: v[:8] if k.startswith("we_") else v for k, v in p.items()}, x)
        want = ref._experts(p, x, arch, None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# The cache by layer kind, NoPE and the attention scale


def test_cache_leaves_exist_only_for_their_kind():
    from repro.models.transformer import abstract_cache, prefill, ApplyCtx

    config, cfg, rcfg, params, _ = _tiny()
    b, w = 3, 24
    cache = abstract_cache(cfg, b, w)["main"]
    n_attn, n_mamba = 1, 3  # [mamba, attention, mamba, mamba]
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (n_attn, b, 2, w, 16), "v": (n_attn, b, 2, w, 16),
        "ssm": (n_mamba, b, 8, 16, 16), "conv": (n_mamba, b, 3, 8 * 16 + 2 * 16)}
    per_attn = 2 * b * 2 * w * 16
    per_mamba = b * (8 * 16 * 16 + 3 * (8 * 16 + 2 * 16))
    assert sum(v.size for v in cache.values()) == n_attn * per_attn + n_mamba * per_mamba
    program = cells.program_module(config)
    assert program.state_bytes(config, b) == 2 * n_mamba * per_mamba
    filled, _ = prefill(ApplyCtx(cfg, rcfg, None), params,
                        {"tokens": jnp.asarray(_tokens()[:, :PROMPT])}, capacity=w)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), filled["main"]) == jax.tree.map(
        lambda a: (a.shape, a.dtype), cache)


def test_nope_adds_no_position():
    """The embedding is the table times 12 at any position, and attention
    gets no rotation: permuting the keys before the query permutes
    nothing in the output of a NoPE layer's attention."""
    from repro.models.transformer import ApplyCtx, _rope_qk, embed

    config, cfg, rcfg, params, _ = _tiny()
    ctx = ApplyCtx(cfg, rcfg, None)
    tokens = jnp.asarray(_tokens()[:, :8])
    for offset in (0, 100):
        pos = jnp.broadcast_to(jnp.arange(8) + offset, tokens.shape)
        np.testing.assert_array_equal(embed(ctx, params, tokens, pos),
                                      params["embed"][tokens] * 12.0)
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
    got = _rope_qk(cfg, q, q, pos, None)
    assert got[0] is q and got[1] is q


@pytest.mark.parametrize("use_pallas", [False, True])
def test_attention_scale_reaches_both_paths(use_pallas):
    """The published 1/128 softmax scale, not 1/sqrt(head_dim), in the
    plain path and in the flash kernel (interpret mode here)."""
    from repro.configs.runtime import RunConfig
    from repro.models.attention import attention

    scale = float(_config()["attention_multiplier"])
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = 4.0 * jax.random.normal(k0, (1, 16, 4, 16))
    k = 4.0 * jax.random.normal(k1, (1, 16, 2, 16))
    v = jax.random.normal(k2, (1, 16, 2, 16))
    pos = jnp.arange(16)[None]
    rcfg = RunConfig(use_pallas=use_pallas, attn_block_q=8, attn_block_k=8)
    got = attention(q, k, v, pos, pos, rcfg=rcfg, scale=scale)
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") * scale
    s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv, precision="highest")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    default = attention(q, k, v, pos, pos, rcfg=rcfg)
    assert np.abs(np.asarray(default - want)).max() > 1e-2


def test_program_counts_every_parameter():
    from repro.models.transformer import abstract_model_params

    program = cells.program_module(_config())
    for config in (_config(), program.tiny(_config()), _tiny()[0]):
        cfg = model.program_config(config)
        tree = abstract_model_params(cfg, model.run_config(config))
        assert program.param_count(config) == sum(a.size for a in jax.tree.leaves(tree))
    # the stated chip share: 4.42 B parameters, 8.84 GB in bf16
    assert program.param_count(_config()) == 4_418_340_096


# ---------------------------------------------------------------------------
# The readers, on synthetic traces

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _run(ops, modules, decodes=(), prefills=()):
    window = types.SimpleNamespace()
    engine = types.SimpleNamespace(decodes=list(decodes), prefills=list(prefills))
    trace = xtrace.Trace([xtrace.Event(*o) for o in ops],
                         [xtrace.Event(n, s, t, 0) for n, s, t in modules], [], 1)
    return reading.RunRecord(_config(), {}, window, engine, PEAKS, trace, 0.0, 100.0,
                             99.0)


def _reader(name):
    return cells.layer_reader(name)


def test_hybrid_decode_hbm_share_reads_bytes_over_device_time():
    config = _config()
    program = cells.program_module(config)
    # two decodes of 32 rows at contexts 600 and 601, 20 ms each
    run = _run([], [("jit_serve_step(4)", 1.0, 1.02), ("jit_serve_step(4)", 2.0, 2.02)],
               decodes=[(0.5, 32, 600), (1.5, 32, 601)])
    moved = program.decode_bytes(config, 32, 600) + program.decode_bytes(config, 32, 601)
    got = _reader("hybrid_decode_hbm_share").read(run)
    assert got == pytest.approx(100.0 * moved / 0.04 / 819e9)
    # by hand: weights, state read and written, K/V of 2 layers
    weights = 2 * 4_418_340_096
    state = 2 * 18 * 32 * (128 * 64 * 128 + 3 * (8192 + 256))
    kv = 2 * 2 * 2 * 32 * 600 * 8 * 128
    assert program.decode_bytes(config, 32, 600) == weights + 2 * state + kv
    assert 50.0 < got < 100.0
    # a program of another name, or a dispatch the trace lacks, reads null
    assert _reader("hybrid_decode_hbm_share").read(
        _run([], [("jit_decode(4)", 1.0, 1.02)], decodes=[(0.5, 32, 600)])) is None
    assert _reader("hybrid_decode_hbm_share").read(
        _run([], [("jit_serve_step(4)", 1.0, 1.02)], decodes=[])) is None


def _kernels(name, n, start, each):
    return [(f"%{name}.{i}", start + i * each, start + (i + 1) * each, 0) for i in range(n)]


def test_moe_experts_time_share_reads_the_grouped_products_of_each_decode():
    # two decodes of 120 ms, each with 60 grouped products of 0.1 ms
    ops = _kernels("gmm", 60, 1.0, 0.0001) + _kernels("gmm", 60, 2.0, 0.0001)
    modules = [("jit_serve_step(4)", 0.9, 1.02), ("jit_serve_step(4)", 1.9, 2.02)]
    got = _reader("moe_experts_time_share").read(_run(ops, modules))
    assert got == pytest.approx(100.0 * 0.012 / 0.24)
    # one product short in a decode, or another kernel name: null
    assert _reader("moe_experts_time_share").read(_run(ops[:-1], modules)) is None
    dense = [("%fusion" + o[0][len("%gmm"):],) + o[1:] for o in ops]
    assert _reader("moe_experts_time_share").read(_run(dense, modules)) is None


def test_readers_read_null_on_a_dense_configuration():
    """A configuration without layer kinds or counts (the dense cells')
    reads null."""
    dense = json.loads((HERE / "configs" / "qwen2.5-3b.json").read_text())
    run = _run(_kernels("gmm", 60, 1.0, 0.0001), [("jit_serve_step(4)", 0.9, 1.02)],
               decodes=[(0.5, 16, 600)])
    run = dataclasses.replace(run, config=dense)
    for name in ("hybrid_decode_hbm_share", "moe_experts_time_share"):
        assert _reader(name).read(run) is None
