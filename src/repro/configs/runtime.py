"""Runtime (non-architecture) configuration: dtypes, remat, block sizes,
sharding rule set, MoE capacity — everything the perf hillclimb tunes."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # attention blocking (XLA online-softmax path; also the Pallas tile hints)
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    # use the blocked path above this many KV positions
    attn_blocked_threshold: int = 2048
    # remat policy for the scanned layer body: none | full | dots
    remat: str = "full"
    # sharding rule set name (see repro.sharding.specs)
    sharding_rules: str = "megatron_fsdp"
    # MoE
    moe_impl: str = "auto"  # dense | expert_parallel | auto
    capacity_factor: float = 1.25
    # decode
    long_context_window: int = 8192
    # Pallas prefill attention / SSD kernels; ``serving_config`` sets it
    # on a TPU backend, training and the CPU tests keep the XLA path
    use_pallas: bool = False
    # decode-time tensor-parallel mode: replicate the (small) activations
    # over the data axes and let the embed-dim contraction reduce with an
    # activation all-reduce, instead of fsdp-gathering the weights every
    # step (§Perf hillclimb #2).
    decode_tp_over_data: bool = False

    @property
    def pdtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return _DTYPES[self.compute_dtype]


def serving_config(**overrides) -> RunConfig:
    """The serving path's RunConfig: no remat, dense MoE, and the Pallas
    prefill kernels exactly when the backend is a TPU, so a chip never
    serves through the XLA attention by default."""
    kw = dict(
        remat="none", moe_impl="dense", use_pallas=jax.default_backend() == "tpu"
    )
    kw.update(overrides)
    return RunConfig(**kw)
