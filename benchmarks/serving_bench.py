"""Serving-runtime benchmarks: the τ-vs-concurrency response curve and the
closed-loop CORAL-over-live-traffic run. Emits BENCH_serving.json.

    PYTHONPATH=src python -m benchmarks.serving_bench        # full
    QUICK=1 PYTHONPATH=src python -m benchmarks.serving_bench  # CI smoke
"""
from __future__ import annotations

from pathlib import Path

from benchmarks.common import begin, emit_json, quick, row

QUICK = quick()


def _engine(batch_size: int = 2, max_len: int = 64):
    from repro.launch.serve import build_engine

    engine = build_engine("qwen2.5-3b", batch_size, max_len)
    return engine, engine.ctx.cfg


def bench_serving_suite():
    """τ vs concurrency (best-of interleaved reps — the container shares
    cores with noisy neighbours, and interference only ever slows a run
    down) + CORAL closed-loop under a bursty trace."""
    from repro.core import tpu_pod_space
    from repro.device.measure import analytic_scale_and_power
    from repro.serving import (
        ServingController,
        ServingRuntime,
        build_serving_record,
        measure_concurrency_curve,
        workload,
    )

    engine, cfg = _engine()
    space = tpu_pod_space()
    c_values = [int(v) for v in space.dims[space.index("concurrency")].values]
    best, rounds = measure_concurrency_curve(
        engine, c_values, rounds=3 if QUICK else 8,
        groups=6 if QUICK else 10, vocab=cfg.vocab,
    )
    for c in c_values:
        row(f"serving_tau_c{c}", 1e6 / max(best[c], 1e-9),
            f"tok_s={best[c]:.0f},x_vs_c1={best[c] / best[1]:.2f}")

    # closed loop: bursty Poisson at ~60% of measured capacity
    cap = max(best.values())
    new_tokens = 8
    iters = 4 if QUICK else 10
    interval_s = 0.3 if QUICK else 0.5
    trace = workload.bursty_poisson(
        rate=0.6 * cap / new_tokens, duration_s=iters * interval_s + 2.0,
        prompt_lens=8, new_tokens=new_tokens, vocab=cfg.vocab, seed=1,
    )
    tau_target = 0.35 * cap
    p_budget = analytic_scale_and_power(
        space.names, space.preset("max_power"))[1] * 0.8
    controller = ServingController(
        ServingRuntime(engine, concurrency=1), space, trace,
        tau_target=tau_target, p_budget=p_budget, interval_s=interval_s,
    )
    outcome, records = controller.run(iters)
    feasible = outcome.feasible(tau_target, p_budget)
    row("serving_closed_loop", sum(r.p99_latency_s for r in records) * 1e6,
        f"feasible={feasible},tau={outcome.tau:.0f}")

    emit_json(
        Path("BENCH_serving.json"),
        build_serving_record(
            "PYTHONPATH=src python -m benchmarks.serving_bench",
            c_values, best, rounds, batch_size=2, iters=iters,
            interval_s=interval_s, tau_target=tau_target, p_budget=p_budget,
            outcome=outcome, records=records,
        ),
    )


def main() -> None:
    begin()
    bench_serving_suite()


if __name__ == "__main__":
    main()
