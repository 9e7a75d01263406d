"""Faults planted beneath the harness, in the program's engine, to show
that the correctness check fails them (tests on the CPU; ``limits.py``
reads the token fault on the chip)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


class AlteredToken:
    """Every ``every``-th decode step produces another token in every row:
    the best is passed over for its neighbour in the vocabulary."""

    def __init__(self, engine, every: int = 3):
        self.engine, self.every, self.calls = engine, every, 0
        self.ctx, self.batch, self.max_len = engine.ctx, engine.batch, engine.max_len

    def prefill(self, tokens, extras=None):
        return self.engine.prefill(tokens, extras)

    def decode(self, cache, tokens):
        cache, logits = self.engine.decode(cache, tokens)
        self.calls += 1
        if self.calls % self.every == 0:
            lg = np.asarray(logits, np.float32)
            rows = np.arange(lg.shape[0])
            lg[rows, -1, (lg[:, -1].argmax(-1) + 1) % lg.shape[-1]] = 1e4
            logits = jnp.asarray(lg, logits.dtype)
        return cache, logits


class StateUnchanged(AlteredToken):
    """A decode step that returns the KV cache it was given."""

    def decode(self, cache, tokens):
        _, logits = self.engine.decode(cache, tokens)
        return cache, logits
