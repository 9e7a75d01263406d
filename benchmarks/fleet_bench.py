"""Fleet-scale bench: tune N heterogeneous device twins in one compiled
``jit(vmap(scan))`` call and emit BENCH_fleet.json.

Twin count: FLEET_TWINS env override, else 64 in QUICK mode (CI smoke),
else 1024 (the paper-scale nightly fleet). Because twin ``i`` is sampled
from ``default_rng([seed, i])`` independently of the fleet size, the
smoke fleet is an exact prefix of the nightly fleet — floors calibrated
on one transfer to the other.

The ``results`` block of the record is deterministic for a given
(n_twins, seed, iters, window); the ``engine`` block is wall-clock and
memory telemetry for the machine that produced it (never gated on
absolute time — benchmarks/check_regression.py gates the deterministic
quality metrics and the warm-start gain ratio only).

    PYTHONPATH=src python -m benchmarks.fleet_bench
    QUICK=1 PYTHONPATH=src python -m benchmarks.fleet_bench
    FLEET_TWINS=256 PYTHONPATH=src python -m benchmarks.fleet_bench
"""
from __future__ import annotations

import os
from pathlib import Path

from benchmarks.common import begin, emit_json, quick, row
from repro.experiments.fleet import FLEET_ITERS, FLEET_WINDOW, run_fleet
from repro.experiments.report import fleet_convergence_figure
from repro.experiments.schema import validate_fleet_record

FLEET_JSON = Path(__file__).resolve().parent.parent / "BENCH_fleet.json"
FLEET_FIG = FLEET_JSON.with_name("FIG_fleet_convergence.png")
QUICK = quick()

FULL_TWINS = 1024
SMOKE_TWINS = 64


def fleet_twins() -> int:
    """Twin count: FLEET_TWINS env override > QUICK smoke > full fleet."""
    raw = os.environ.get("FLEET_TWINS")
    if raw:
        return int(raw)
    return SMOKE_TWINS if QUICK else FULL_TWINS


def bench_fleet_suite() -> dict:
    n = fleet_twins()
    rec = run_fleet(
        n_twins=n,
        seed=0,
        iters=FLEET_ITERS,
        window=FLEET_WINDOW,
        probe_steady=True,
    )
    res, eng = rec["results"], rec["engine"]
    payload = {
        "schema_version": 1,
        "regenerate": "PYTHONPATH=src python -m benchmarks.fleet_bench",
        "quick": QUICK,
        "results": res,
        "engine": eng,
    }
    validate_fleet_record(payload)
    emit_json(FLEET_JSON, payload)
    row(
        f"fleet_cold_n{n}",
        eng["cold_wall_s"] * 1e6,
        f"feasible_rate={res['feasible_rate']:.3f} "
        f"mean_m2f={res['mean_m2f_cold']}",
    )
    row(
        f"fleet_warm_n{res['warm_matched']}",
        eng["warm_wall_s"] * 1e6,
        f"m2f cold={res['mean_m2f_cold_cohort']} "
        f"warm={res['mean_m2f_warm_cohort']} gain={res['warm_gain']}x",
    )
    if eng.get("twins_per_s") is not None:
        row(
            "fleet_steady_throughput",
            eng["steady_wall_s"] * 1e6,
            f"{eng['twins_per_s']:.0f} twins/s (post-compile, "
            f"{res['iters']} iters each)",
        )
    row(
        "fleet_memory",
        0.0,
        f"tables={eng['table_bytes']}B batch={eng['batch_bytes']}B "
        f"consts={eng['consts_bytes']}B",
    )
    row("fleet_json", 0.0, f"wrote {FLEET_JSON.name}")
    fig = fleet_convergence_figure(payload, str(FLEET_FIG))
    row(
        "fleet_figure",
        0.0,
        f"wrote {FLEET_FIG.name}" if fig else "skipped (no matplotlib)",
    )
    return payload


if __name__ == "__main__":
    begin()
    bench_fleet_suite()
