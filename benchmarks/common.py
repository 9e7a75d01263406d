"""Shared helpers for the benchmark harness — including the single
source of truth for the env flags CI and local runs both read.

Flags (see also tests/conftest.py, which re-exports the same helpers so
the test suite and the benches cannot drift):

  QUICK                — CI-smoke mode: fewer iterations/seeds everywhere.
  SERVING_PERF_STRICT  — keep the concurrency-gain perf gates hard
                         (default on; hosted runners set 0 to demote the
                         host-headroom-dependent gates to skips).
  PALLAS_INTERPRET     — Pallas interpret mode; follows the jax backend
                         (interpreted off a TPU, compiled on one) and a
                         value that contradicts it is an error.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Callable, Optional

def env_flag(name: str, default: bool = False) -> bool:
    """Truthiness of an env var: unset → ``default``; "", 0, false, no →
    False; anything else → True. Delegates to ``repro.envflags`` — the
    single shared truthy-parser — so "QUICK=" and "QUICK=0" mean the same
    thing in every entry point, bench or kernel."""
    from repro.envflags import env_flag as _env_flag

    return _env_flag(name, default)


def quick() -> bool:
    """CI-smoke mode (QUICK=1)."""
    return env_flag("QUICK")


def serving_perf_strict() -> bool:
    """Whether host-headroom-dependent serving perf gates are hard
    failures (default) or skips (SERVING_PERF_STRICT=0)."""
    return env_flag("SERVING_PERF_STRICT", default=True)


def pallas_interpret() -> Optional[bool]:
    """Explicit PALLAS_INTERPRET override, or None for backend-auto.
    Delegates to the kernels' canonical routing point
    (``repro.kernels.runtime``, built on the same ``repro.envflags``
    parser) so the harness helper and ``default_interpret`` cannot
    drift."""
    from repro.kernels.runtime import parse_interpret_env

    return parse_interpret_env(os.environ.get("PALLAS_INTERPRET"))


def begin() -> None:
    """First call of every bench entry point: place JAX's persistent
    compile cache (``repro.compile_cache``) and print the CSV header."""
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")


def timeit(fn: Callable, iters: int = 3, warmup: int = 1) -> float:
    """Mean wall-time per call in microseconds."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def emit_json(path: Path, payload: dict) -> None:
    """Write a machine-readable benchmark record (sorted keys, trailing
    newline) so successive PRs can diff the perf trajectory."""
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
