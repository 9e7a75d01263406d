"""Serving engine: batched prefill + decode against a KV cache.

``make_prefill_step`` / ``make_serve_step`` are the jit-able step functions
the multi-pod dry-run lowers; ``make_engine_decode`` is the decode program
``ServingEngine`` dispatches (its KV storage donated and updated in place);
``ServingEngine`` is the runnable host-side loop used by examples and by
the WalltimeDevice (real measured throughput for the CORAL optimizer).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import (
    ApplyCtx,
    decode_step,
    prefill,
)


def make_prefill_step(ctx: ApplyCtx, capacity=None):
    """Jit-able prefill step ``(params, batch) -> (cache, logits)`` for
    a fixed model context; ``capacity`` pads the KV cache length."""

    def prefill_step(params, batch):
        return prefill(ctx, params, batch, capacity=capacity)

    return prefill_step


def make_serve_step(ctx: ApplyCtx):
    """Jit-able single-token decode step
    ``(params, cache, tokens) -> (cache, logits)``."""

    def serve_step(params, cache, tokens):
        return decode_step(ctx, params, cache, tokens)

    return serve_step


def make_engine_decode(ctx: ApplyCtx):
    """The decode program ``ServingEngine`` dispatches:
    ``(params, kv, length, tokens) -> (kv, length, logits)``, ``kv`` the
    cache without ``length``. ``kv`` is donated, so the step writes the
    new token into the given storage in place; ``length`` is not, so an
    older handle keeps its own."""
    step = make_serve_step(ctx)

    def serve_step(params, kv, length, tokens):
        cache, logits = step(params, {**kv, "length": length}, tokens)
        length = cache.pop("length")
        return cache, length, logits

    return jax.jit(serve_step, donate_argnums=(1,))


class ServingEngine:
    """Greedy-decoding engine over batch-aligned request groups.

    Concurrency (the CORAL knob ``c``) is modeled as multiple in-flight
    request groups: host-side token sampling/bookkeeping of group i
    overlaps device compute of group j, as on a real serving host.

    ``decodes`` counts decode dispatches; ``kv_in_place`` counts those
    whose given KV storage JAX consumed (donated). The two are equal
    unless the backend could not donate, and then each step copies the
    cache.
    """

    def __init__(self, ctx: ApplyCtx, params, batch_size: int, max_len: int):
        self.ctx = ctx
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self._prefill = jax.jit(make_prefill_step(ctx, capacity=max_len))
        self._decode = make_engine_decode(ctx)
        self.decodes = 0
        self.kv_in_place = 0

    def prefill(self, tokens: np.ndarray, extras: Optional[Dict] = None):
        batch = {"tokens": jnp.asarray(tokens)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        cache, logits = self._prefill(self.params, batch)
        return cache, logits

    def decode(self, cache, tokens):
        """One decode step. Dispatch is asynchronous: the returned
        (cache, logits) are device futures, which is what lets the runtime
        keep ``c`` groups in flight on the device queue.

        The step consumes the KV storage of ``cache``: it is donated and
        updated in place. The given handle then refers to the group's
        updated storage at its own, unchanged ``length`` (every handle of
        a group shares the per-stack dicts, which are rebound to the new
        arrays). Decoding such a stale handle writes at its old slot and
        attends to slots up to it; the later slots are masked, so its
        attention reads as a functional decode of that handle would,
        within capacity. Recurrent (SSM/conv) state has no slots: a stale
        handle reads it as the latest decode of the group left it."""
        kv = {k: v for k, v in cache.items() if k != "length"}
        probe = jax.tree.leaves(kv)[0]
        new_kv, length, logits = self._decode(
            self.params, kv, cache["length"], tokens
        )
        self.decodes += 1
        self.kv_in_place += probe.is_deleted()
        for stack, leaves in new_kv.items():
            kv[stack].update(leaves)
        return {**kv, "length": length}, logits

    def generate(
        self,
        prompt: np.ndarray,
        n_tokens: int,
        extras: Optional[Dict] = None,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> np.ndarray:
        cache, logits = self.prefill(prompt, extras)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, temperature, key)
        for i in range(n_tokens):
            out.append(np.asarray(tok))
            cache, logits = self.decode(cache, tok)
            key, sub = jax.random.split(key)
            tok = self._sample(logits, temperature, sub)
        return np.concatenate(out, axis=1)

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits[:, -1] / temperature, axis=-1
        )[:, None].astype(jnp.int32)

    # NOTE: throughput probing lives in repro.serving.runtime
    # (measure_runtime_throughput / measure_concurrency_curve) so every
    # reported number comes from the same continuous-batching path.
