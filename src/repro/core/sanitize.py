"""Runtime sanitizer lanes for the episode engine.

Two independent tools, both zero-cost when off:

- **checkify lane** (``REPRO_CHECKIFY=1``): wraps the episode scan in
  ``jax.experimental.checkify`` with NaN/div error checks (see
  :func:`checkify_errors` for why OOB index checks are excluded). The
  compiled program then carries error state through the scan and the
  host raises on the first poisoned value — turning a silent NaN in the
  reward or an out-of-bounds gather into a hard failure with a payload.
  ``core/episode.py::_compiled_runner`` keys its executable cache on
  the flag, so flipping it mid-process can never serve a stale program.

- **compile-count guard**: :func:`count_compiles` captures
  ``jax.log_compiles`` output and counts executable builds per jitted
  function name. One ``Compiling jit(<name>) with global shapes`` line is
  emitted per build — including persistent-compilation-cache hits
  (deserialization still lowers), and never on in-process jit cache
  reuse — so "the static matrix compiles exactly once per engine spec"
  is assertable in CI regardless of the cache state.
"""
from __future__ import annotations

import logging
import re
from contextlib import contextmanager
from typing import Iterator, List

import jax

from repro.envflags import env_flag


def checkify_enabled() -> bool:
    """The REPRO_CHECKIFY=1 lane (single parser: envflags)."""
    return env_flag("REPRO_CHECKIFY")


def checkify_errors():
    """NaN + div-by-zero (+ explicit checkify.check assertions).

    ``checkify.index_checks`` is deliberately absent: this jax release
    crashes inside its own scatter OOB rule (``checkify.py::scatter_oob``
    raises IndexError) on the engine's vmapped ``.at[slot].set`` ring
    updates. OOB gathers are instead covered by the engine's explicit
    sentinel clamps plus the contracts lane's shape checks."""
    from jax.experimental import checkify

    return checkify.float_checks | checkify.div_checks


def wrap_checkify(fn):
    """checkify ``fn`` and re-pack as same-signature callable that
    raises on the first poisoned value. Argument positions are
    preserved, so an enclosing ``jax.jit(..., donate_argnums=...)``
    keeps donating the same buffers."""
    from jax.experimental import checkify

    return checkify.checkify(fn, errors=checkify_errors())


_COMPILING = re.compile(r"^Compiling jit\(([^\s()]+)\) with global shapes")


class CompileLog:
    """Captured executable builds: ``total`` across all jitted names,
    ``count(name)`` per function name."""

    def __init__(self) -> None:
        self.names: List[str] = []

    @property
    def total(self) -> int:
        return len(self.names)

    def count(self, name: str) -> int:
        return sum(1 for n in self.names if n == name)


class _Capture(logging.Handler):
    def __init__(self, log: CompileLog) -> None:
        super().__init__(level=logging.DEBUG)
        self._log = log

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILING.match(record.getMessage())
        if m:
            self._log.names.append(m.group(1))


@contextmanager
def count_compiles() -> Iterator[CompileLog]:
    """Count executable builds inside the block::

        with count_compiles() as cc:
            runner(batch, tables)
        assert cc.count("run") == 1   # one build of the episode scan
        with count_compiles() as cc2:
            runner(batch2, tables2)   # same spec, fresh data
        assert cc2.total == 0         # no recompilation

    Attaches one handler to the root ``jax`` logger (child records
    propagate there exactly once)."""
    log = CompileLog()
    handler = _Capture(log)
    jax_logger = logging.getLogger("jax")
    jax_logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            yield log
    finally:
        jax_logger.removeHandler(handler)
