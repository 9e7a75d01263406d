"""Compile-only checks for one chip of a described TPU v5e (``v5e:2x2``).

Nothing runs: each case lowers a kernel or a whole step for the chip's
compiler, which refuses what interpret mode cannot see — slices off the
(8, 128) tiling, scoped-VMEM overruns, programs larger than HBM. The
topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker given
this file loads the TPU compiler.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.configs.runtime import serving_config
from repro.models.transformer import (
    ApplyCtx,
    abstract_cache,
    abstract_model_params,
)
from repro.serving.engine import make_prefill_step, make_serve_step

HBM_BYTES = 15.75 * 2**30  # one v5e chip's usable HBM, as the compiler counts


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def test_dcov_gram_default_block_n2048(one_chip):
    from repro.kernels.dcov.dcov import dcov_gram_pallas

    c = _compile(
        lambda x: dcov_gram_pallas(x, interpret=False),
        _sds(one_chip, (2048, 6)),
    )
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_mamba2_widths(one_chip):
    """mamba2-2.7b: 80 heads of 64, state 128, chunk 256."""
    from repro.kernels.ssd_scan.ssd_scan import ssd_pallas

    b, s, nh, hd, n = 1, 1024, 80, 64, 128
    bf = jnp.bfloat16
    c = _compile(
        lambda *a: ssd_pallas(*a, chunk=256, interpret=False),
        _sds(one_chip, (b, s, nh, hd), bf),
        _sds(one_chip, (b, s, nh)),
        _sds(one_chip, (nh,)),
        _sds(one_chip, (b, s, n), bf),
        _sds(one_chip, (b, s, n), bf),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("seq", [128, 512])
def test_flash_attention_qwen_prefill_widths(one_chip, seq):
    """qwen2.5-3b prefill: 16 query heads over 2 KV heads of 128."""
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_bhsd,
    )

    bf = jnp.bfloat16
    c = _compile(
        lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False),
        _sds(one_chip, (4, 16, seq, 128), bf),
        _sds(one_chip, (4, 2, seq, 128), bf),
        _sds(one_chip, (4, 2, seq, 128), bf),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture
def qwen_full(one_chip, monkeypatch):
    """Full-width qwen2.5-3b with bf16 params as the chip serves it: the
    Pallas prefill kernel on, compiled (the interpret default sees the
    CPU here, so the test steers it)."""
    # the package re-exports a function under the module's name
    fa = importlib.import_module("repro.kernels.flash_attention.flash_attention")
    monkeypatch.setattr(fa, "default_interpret", lambda: False)
    cfg = get_config("qwen2.5-3b")
    rcfg = serving_config(param_dtype="bfloat16", use_pallas=True)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        abstract_model_params(cfg, rcfg),
    )
    return ApplyCtx(cfg, rcfg, None), params


BATCH, PROMPT, CAPACITY = 4, 128, 161


def test_qwen_full_prefill_fits_hbm(one_chip, qwen_full):
    ctx, params = qwen_full
    tokens = _sds(one_chip, (BATCH, PROMPT), jnp.int32)
    c = _compile(
        lambda p, t: make_prefill_step(ctx, capacity=CAPACITY)(p, {"tokens": t}),
        params,
        tokens,
    )
    assert "tpu_custom_call" in c.as_text()  # the Pallas flash kernel
    assert _hbm_bytes(c) <= HBM_BYTES, c.memory_analysis()


def test_qwen_full_decode_fits_hbm(one_chip, qwen_full):
    ctx, params = qwen_full
    cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        abstract_cache(ctx.cfg, BATCH, CAPACITY),
    )
    tokens = _sds(one_chip, (BATCH, 1), jnp.int32)
    c = _compile(make_serve_step(ctx), params, cache, tokens)
    assert _hbm_bytes(c) <= HBM_BYTES, c.memory_analysis()
