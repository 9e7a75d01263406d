"""Canonical interpret-mode routing for every Pallas kernel in the repo.

A kernel must never derive its own ``interpret=`` value (that is lint
rule RL05): the decision lives here, in one place, so the dcov, flash
attention and SSD-scan entry points — and the bench harness view
``benchmarks.common.pallas_interpret`` — can never disagree about
whether the Mosaic compiler or the interpreter runs a kernel.

Resolution:

- On a TPU backend the kernels always compile with Mosaic. A
  ``PALLAS_INTERPRET`` value asking for interpret mode there is an
  error, never a silent switch to the interpreter.
- Elsewhere the kernels run in interpret mode. ``PALLAS_INTERPRET``
  (parsed by :mod:`repro.envflags`) can only confirm that: asking for
  compiled mode off a TPU is an error too, since Mosaic cannot run there.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro.envflags import parse_tristate


def parse_interpret_env(raw: Optional[str]) -> Optional[bool]:
    """The one parser for PALLAS_INTERPRET: ``None`` for unset/empty
    (backend-auto), else :func:`repro.envflags.truthy`."""
    return parse_tristate(raw)


def default_interpret() -> bool:
    """Interpret mode exactly when the backend is not a TPU. A
    PALLAS_INTERPRET value that contradicts the backend raises."""
    interpret = jax.default_backend() != "tpu"
    env = parse_interpret_env(os.environ.get("PALLAS_INTERPRET"))
    if env is not None and env != interpret:
        raise RuntimeError(
            f"PALLAS_INTERPRET={os.environ['PALLAS_INTERPRET']!r} asks for "
            f"{'interpret' if env else 'compiled'} Pallas kernels on the "
            f"{jax.default_backend()!r} backend, which runs them "
            f"{'interpreted' if interpret else 'compiled'}; unset it"
        )
    return interpret
