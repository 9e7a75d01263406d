"""Operations and bytes of the serving path, from shapes alone.

Model FLOPs count the multiply-adds the algorithm needs (2 per MAC):
every projection, causal attention over the positions that exist (no
padding, no masked tiles), and the head only where the program computes
logits. Bytes are the least the device must move: each weight once per
step, the keys and values that are live, activations at the kernel's
edges. All in bfloat16 (2 bytes), as served.
"""
from __future__ import annotations

import dataclasses

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    hq: int
    hkv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    tied: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        """The sizes of a configuration file; the head size is its
        ``head_dim`` where it gives one, else hidden ÷ query heads."""
        d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
        return cls(
            d=d,
            hq=hq,
            hkv=int(config["num_key_value_heads"]),
            hd=int(config.get("head_dim") or d // hq),
            ff=int(config["intermediate_size"]),
            vocab=int(config["vocab_size"]),
            layers=int(config["num_hidden_layers"]),
            tied=bool(config["tie_word_embeddings"]),
        )

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o and the three SwiGLU matrices of one layer."""
        attn = 2 * self.d * self.hq * self.hd + 2 * self.d * self.hkv * self.hd
        return attn + 3 * self.d * self.ff

    @property
    def head_params(self) -> int:
        return self.d * self.vocab


def attn_flops(dims: Dims, rows: int, q_len: int, kv_start: int) -> float:
    """QK^T and PV of ``q_len`` causal queries at positions
    kv_start..kv_start+q_len-1, each over the keys up to itself."""
    keys = q_len * kv_start + q_len * (q_len + 1) / 2
    return 4.0 * rows * dims.hq * dims.hd * keys


def prefill_flops(dims: Dims, rows: int, seq: int) -> float:
    """One prefill of ``rows`` prompts of ``seq`` tokens, with the head at
    the last position only."""
    per_layer = 2.0 * rows * seq * dims.layer_matmul_params + attn_flops(
        dims, rows, seq, 0
    )
    return dims.layers * per_layer + 2.0 * rows * dims.head_params


def decode_flops(dims: Dims, rows: int, context: int) -> float:
    """One decode step of ``rows`` tokens, each attending to ``context``
    positions (itself included)."""
    per_layer = 2.0 * rows * dims.layer_matmul_params + attn_flops(
        dims, rows, 1, context - 1
    )
    return dims.layers * per_layer + 2.0 * rows * dims.head_params


def token_flops(dims: Dims, prompt_len: int, index: int) -> float:
    """Model FLOPs of one request's ``index``-th output token: its share
    of the prefill for the first, one decode row after that."""
    if index == 0:
        return prefill_flops(dims, 1, prompt_len)
    return decode_flops(dims, 1, prompt_len + index)


def weight_bytes(dims: Dims) -> float:
    """Weights a decode step streams: every layer matrix and the head."""
    return BF16 * (dims.layers * dims.layer_matmul_params + dims.head_params)


def kv_bytes(dims: Dims, rows: int, context: int) -> float:
    """Keys and values of ``context`` live positions, every layer."""
    return BF16 * 2.0 * dims.layers * rows * context * dims.hkv * dims.hd


def decode_bytes(dims: Dims, rows: int, context: int) -> float:
    return weight_bytes(dims) + kv_bytes(dims, rows, context)


def flash_flops(dims: Dims, rows: int, seq: int) -> float:
    """One flash-attention call (one layer) over a causal prompt."""
    return attn_flops(dims, rows, seq, 0)


def flash_bytes(dims: Dims, rows: int, seq: int) -> float:
    """q and k, v read once, the output written once."""
    return BF16 * rows * seq * dims.hd * (2 * dims.hq + 2 * dims.hkv)
