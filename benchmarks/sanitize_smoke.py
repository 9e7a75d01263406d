"""Sanitizer-lane smoke: run the engine with REPRO_CHECKIFY=1 and
REPRO_CONTRACTS=1 forced on, so CI proves the instrumented executables
stay healthy (no checkify poison, no contract drift) on every push.

Covers the two load-bearing engine paths:

- one dual-constraint static cell (jetson-like space, vmapped seeds);
- the fleet path at FLEET_TWINS twins (default 64 — the CI smoke
  prefix of the nightly 1024-twin fleet).

No JSON is emitted: this is a gate, not a tracked benchmark — the
checkified executables are deliberately not comparable to the plain
engine's telemetry.

    PYTHONPATH=src python -m benchmarks.sanitize_smoke
    FLEET_TWINS=16 PYTHONPATH=src python -m benchmarks.sanitize_smoke
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import begin, row


def force_lanes() -> None:
    """Force both lanes before any engine call builds an executable —
    the lane flags are read at call time and the runner cache is keyed
    on the checkify flag, so this cannot leak a stale executable into
    other entry points."""
    os.environ["REPRO_CHECKIFY"] = "1"
    os.environ["REPRO_CONTRACTS"] = "1"


def smoke_static_cell() -> None:
    from repro.core.episode import run_coral_batch
    from repro.core.evaluate import RegimeTargets
    from repro.core.space import jetson_like_space
    from repro.device import jetson_like_simulator

    space = jetson_like_space()
    sim = jetson_like_simulator(space)
    lt, lp = sim.exact_all()
    # jointly satisfiable dual cell: throughput floor taken from the
    # configs inside the power envelope
    p_budget = float(np.percentile(lp, 70))
    targets = RegimeTargets(
        mode="dual",
        tau_target=float(np.percentile(lt[lp <= p_budget], 50)),
        p_budget=p_budget,
    )
    t0 = time.perf_counter()
    eps = run_coral_batch(space, lt, lp, targets, seeds=(0, 1, 2, 3))
    wall = time.perf_counter() - t0
    ok = sum(
        ep.outcome.feasible(targets.tau_target, targets.p_budget)
        for ep in eps
    )
    row(
        "sanitize_static_dual",
        wall * 1e6 / len(eps),
        f"checkify+contracts clean, feasible={ok}/{len(eps)}",
    )


def smoke_fleet() -> None:
    from repro.experiments.fleet import run_fleet

    n = int(os.environ.get("FLEET_TWINS") or 64)
    t0 = time.perf_counter()
    rec = run_fleet(n_twins=n, seed=0, probe_steady=False)
    wall = time.perf_counter() - t0
    res = rec["results"]
    row(
        f"sanitize_fleet_n{n}",
        wall * 1e6 / n,
        f"checkify+contracts clean, feasible_rate={res['feasible_rate']:.3f}",
    )


if __name__ == "__main__":
    force_lanes()
    begin()
    smoke_static_cell()
    smoke_fleet()
