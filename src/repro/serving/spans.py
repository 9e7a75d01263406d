"""Host spans of the serving loop, on the ``jax.profiler`` trace's clock.

Each boundary of a ring pass opens one ``jax.profiler.TraceAnnotation``
under a name below. The spans are flat (none opens inside another), and
the dispatch and retire spans carry ``group``: the ring's count of groups
formed when the group was admitted, so one group's spans can be followed
across passes. Outside an active trace a span costs about a microsecond.
"""
from __future__ import annotations

import jax

ADMIT = "serve.admit"  # the pool scan that forms a group for one idle slot
PREFILL_DISPATCH = "serve.prefill.dispatch"  # stack the prompts, dispatch the prefill
RETIRE_WAIT = "serve.retire.wait"  # the host waits for the slot's logits
RETIRE_COPY = "serve.retire.copy"  # (B, 1, V) logits device → host
RETIRE_SAMPLE = "serve.retire.sample"  # host argmax
RETIRE_BOOK = "serve.retire.book"  # per-row tokens, completions, the token record
DECODE_DISPATCH = "serve.decode.dispatch"  # dispatch the group's next decode


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name``; ``args`` are recorded with it in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)
