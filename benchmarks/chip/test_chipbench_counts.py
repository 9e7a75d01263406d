"""FLOP and byte functions against hand counts at the cells' shapes."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import counts  # noqa: E402


def _dims(name):
    return counts.Dims.of(json.loads((HERE / "configs" / f"{name}.json").read_text()))


def test_qwen_dims_and_parameters():
    d = _dims("qwen2.5-3b")
    assert (d.d, d.hq, d.hkv, d.hd, d.ff, d.vocab, d.layers, d.tied) == (
        2048, 16, 2, 128, 11008, 151936, 36, True)
    # q 2048x2048, k and v 2048x256 each, o 2048x2048, three 2048x11008
    per_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert d.layer_matmul_params == per_layer == 77_070_336
    # tied: the head is the embedding, read once per step
    assert counts.weight_bytes(d) == 2 * (36 * 77_070_336 + 2048 * 151936)


def test_granite_dims_and_parameters():
    d = _dims("granite-8b")
    per_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert d.layer_matmul_params == per_layer == 218_103_808
    assert counts.weight_bytes(d) == 2 * (18 * per_layer + 4096 * 49152)


def test_head_dim_from_the_file_where_it_gives_one():
    config = json.loads((HERE / "configs" / "qwen2.5-3b.json").read_text())
    assert "head_dim" not in config and counts.Dims.of(config).hd == 2048 // 16
    d = counts.Dims.of(dict(config, head_dim=64))
    assert d.hd == 64
    # q and o 2048x(16*64), k and v 2048x(2*64), three 2048x11008
    assert d.layer_matmul_params == 2 * 2048 * 1024 + 2 * 2048 * 128 + 3 * 2048 * 11008


def test_prefill_flops_by_hand_qwen():
    d = _dims("qwen2.5-3b")
    b, s = 16, 512
    proj = 2 * b * s * 77_070_336
    # causal: query i sees i+1 keys -> s(s+1)/2 pairs, 2 matmuls x 2 flops x hd
    attn = 4 * b * 16 * 128 * (s * (s + 1) // 2)
    head = 2 * b * 2048 * 151936  # last position only
    assert counts.prefill_flops(d, b, s) == pytest.approx(36 * (proj + attn) + head)


def test_decode_flops_and_bytes_by_hand_granite():
    d = _dims("granite-8b")
    b, ctx = 16, 700
    proj = 2 * b * 218_103_808
    attn = 4 * b * 32 * 128 * ctx  # one query over ctx keys
    head = 2 * b * 4096 * 49152
    assert counts.decode_flops(d, b, ctx) == pytest.approx(18 * (proj + attn) + head)
    kv = 2 * 2 * 18 * b * ctx * 8 * 128  # bf16, k and v
    assert counts.decode_bytes(d, b, ctx) == pytest.approx(counts.weight_bytes(d) + kv)


def test_token_flops_first_is_prefill_then_decode():
    d = _dims("qwen2.5-3b")
    assert counts.token_flops(d, 256, 0) == counts.prefill_flops(d, 1, 256)
    assert counts.token_flops(d, 256, 3) == counts.decode_flops(d, 1, 259)


def test_flash_flops_and_bytes_by_hand():
    d = _dims("qwen2.5-3b")
    b, s = 8, 2048
    assert counts.flash_flops(d, b, s) == pytest.approx(4 * b * 16 * 128 * s * (s + 1) / 2)
    # q and o: 16 heads each; k and v: 2 heads each; bf16
    assert counts.flash_bytes(d, b, s) == 2 * b * s * 128 * (16 + 16 + 2 + 2)
    # at this size the kernel is compute-bound on a v5e
    assert counts.flash_flops(d, b, s) / 197e12 > counts.flash_bytes(d, b, s) / 819e9
