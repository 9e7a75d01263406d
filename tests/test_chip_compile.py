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
import re

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
from repro.serving.engine import (
    make_engine_decode,
    make_prefill_step,
    make_serve_step,
)

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


OFFLINE_BATCH, OFFLINE_CAPACITY = 16, 1024  # the offline cells' decode
_OP = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = bf16\[([\d,]*)\]", re.M)


def _computation(text: str, name: str) -> str:
    start = re.search(rf"^{re.escape(name)} ", text, re.M).start()
    return text[start:text.index("\n}", start)]


def test_qwen_full_engine_decode_updates_cache_in_place(one_chip, qwen_full):
    """The decode the engine dispatches, at the offline cells' shapes:
    the KV cache is aliased to the output, nothing copies the whole
    cache, and the layer loop holds no per-layer K/V slab: attention reads
    each layer's slab from the carried cache in place."""
    ctx, params = qwen_full
    cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        abstract_cache(ctx.cfg, OFFLINE_BATCH, OFFLINE_CAPACITY),
    )
    kv = {k: v for k, v in cache.items() if k != "length"}
    tokens = _sds(one_chip, (OFFLINE_BATCH, 1), jnp.int32)
    c = make_engine_decode(ctx).lower(
        params, kv, cache["length"], tokens).compile()
    k = cache["main"]["k"]
    assert c.memory_analysis().alias_size_in_bytes >= 2 * k.size * 2
    assert _hbm_bytes(c) <= HBM_BYTES, c.memory_analysis()

    def dims(d):  # an output's dims, size-1 axes dropped, in any order
        return sorted(int(x) for x in d.split(",") if x not in ("", "1"))

    text = c.as_text()
    copies = [n for n, d in _OP.findall(text)
              if n.startswith("%copy") and dims(d) == sorted(k.shape)]
    assert not copies, copies
    (body,) = set(re.findall(r"body=(%[\w.\-]+)", text))
    slabs = [n for n, d in _OP.findall(_computation(text, body))
             if dims(d) == sorted(k.shape[1:])]
    assert not slabs, slabs


@pytest.fixture
def granite_h_full(one_chip, monkeypatch):
    """granite-4.0-h-small's chip share (20 layers, 9 of 72 experts) with
    bf16 params as the benchmark serves it: the flash, SSD and grouped
    expert kernels compiled, not interpreted."""
    import importlib.util
    import json
    from pathlib import Path

    import repro.kernels.runtime as kernel_runtime

    for mod in ("repro.kernels.flash_attention.flash_attention",
                "repro.kernels.ssd_scan.ssd_scan"):
        monkeypatch.setattr(importlib.import_module(mod), "default_interpret",
                            lambda: False)
    monkeypatch.setattr(kernel_runtime, "default_interpret", lambda: False)
    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    spec = importlib.util.spec_from_file_location(
        "granite_moe_hybrid_program", chip / "programs" / "granite_moe_hybrid.py")
    program = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(program)
    config = json.loads((chip / "configs" / "granite-4.0-h-small.json").read_text())
    cfg = program.program_config(config)
    rcfg = serving_config(param_dtype="bfloat16", use_pallas=True)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        abstract_model_params(cfg, rcfg),
    )
    return ApplyCtx(cfg, rcfg, None), params


HYBRID_BATCH, HYBRID_PROMPT, HYBRID_CAPACITY = 32, 512, 1024  # the offline_b32 cell


def test_granite_h_engine_decode_updates_state_in_place(one_chip, granite_h_full):
    """The hybrid's decode at the cell's shapes fits HBM with every cache
    leaf (K/V of 2 attention layers, SSM and conv state of 18 Mamba
    layers) aliased to the output, no copy of the SSM state or K/V, and
    the held experts in the grouped kernel. (The 58 MB conv leaf is
    copied into fast memory once a step, as mamba2-2.7b's state is.)"""
    ctx, params = granite_h_full
    cache = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        abstract_cache(ctx.cfg, HYBRID_BATCH, HYBRID_CAPACITY),
    )
    kv = {k: v for k, v in cache.items() if k != "length"}
    tokens = _sds(one_chip, (HYBRID_BATCH, 1), jnp.int32)
    c = make_engine_decode(ctx).lower(params, kv, cache["length"], tokens).compile()
    leaves = cache["main"]
    assert set(leaves) == {"k", "v", "ssm", "conv"}
    assert leaves["ssm"].shape == (18, 32, 128, 64, 128)
    assert c.memory_analysis().alias_size_in_bytes >= sum(
        a.size * 2 for a in leaves.values())
    assert _hbm_bytes(c) <= HBM_BYTES, c.memory_analysis()
    text = c.as_text()
    for name in ("ssm", "k"):
        shape = ",".join(str(n) for n in leaves[name].shape)
        assert not re.search(rf"= bf16\[{shape}\]\S* copy\(", text), name
    assert "gmm" in text and "tpu_custom_call" in text


def test_granite_h_prefill_fits_hbm(one_chip, granite_h_full):
    """The prefill of 32 prompts of 512 (two SSD chunks) with the Pallas
    SSD, flash and grouped expert kernels fits HBM."""
    ctx, params = granite_h_full
    tokens = _sds(one_chip, (HYBRID_BATCH, HYBRID_PROMPT), jnp.int32)
    c = _compile(
        lambda p, t: make_prefill_step(ctx, capacity=HYBRID_CAPACITY)(p, {"tokens": t}),
        params,
        tokens,
    )
    text = c.as_text()
    for kernel in ("ssd_pallas", "flash_attention_bhsd", "gmm"):
        assert f"%{kernel}" in text, kernel
    assert _hbm_bytes(c) <= HBM_BYTES, c.memory_analysis()
