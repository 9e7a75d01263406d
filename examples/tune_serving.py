"""CORAL closed-loop against the *live* continuous-batching runtime.

Boots a reduced model, measures the real τ-vs-concurrency response of this
host (the knob the old sequential scheduler ignored), then runs CORAL
closed-loop against live bursty traffic: apply the proposed config to the
runtime (concurrency for real, DVFS as pacing), serve one control
interval of the trace, observe windowed (τ, p), repeat. Emits the
per-interval trace and BENCH_serving.json.

    PYTHONPATH=src python examples/tune_serving.py
"""
import json

from repro.core import tpu_pod_space
from repro.device.measure import analytic_scale_and_power
from repro.launch.serve import build_engine
from repro.serving import (
    ServingController,
    ServingRuntime,
    build_serving_record,
    measure_concurrency_curve,
    workload,
)

engine = build_engine("qwen2.5-3b", batch=2, max_len=64)
cfg = engine.ctx.cfg
space = tpu_pod_space()

# 1) measured τ vs concurrency — identical workload per level, the knob is
#    the only variable (bit-identical across c before this runtime existed)
c_values = [int(v) for v in space.dims[space.index("concurrency")].values]
curve, rounds = measure_concurrency_curve(engine, c_values, rounds=3,
                                          vocab=cfg.vocab)
print("measured decode throughput vs concurrency:")
for c, tau in curve.items():
    print(f"  c={c}: {tau:7.0f} tok/s  ({tau / curve[1]:.2f}x vs c=1)")

# 2) CORAL closed-loop under a bursty Poisson trace at ~60% of capacity
cap = max(curve.values())
new_tokens = 8
iters, interval_s = 10, 0.5
trace = workload.bursty_poisson(
    rate=0.6 * cap / new_tokens, duration_s=iters * interval_s + 2.0,
    prompt_lens=8, new_tokens=new_tokens, vocab=cfg.vocab, seed=1,
)
tau_target = 0.45 * cap
p_budget = analytic_scale_and_power(space.names, space.preset("max_power"))[1] * 0.8
runtime = ServingRuntime(engine, concurrency=1)
controller = ServingController(
    runtime, space, trace, tau_target=tau_target, p_budget=p_budget,
    interval_s=interval_s,
)
outcome, records = controller.run(iters)

print(f"\nclosed loop ({iters} control intervals of {interval_s}s, "
      f"target ≥{tau_target:.0f} tok/s, budget ≤{p_budget / 1e3:.2f} kW):")
for k, r in enumerate(records):
    print(f"  [{k}] c={int(r.config[-1])} f={r.config[2]:.0f}MHz "
          f"τ={r.tau:7.0f} tok/s p={r.power / 1e3:.2f}kW r={r.reward:8.2f} "
          f"queue={r.queue_depth} p99={r.p99_latency_s * 1e3:.0f}ms")
print(f"CORAL found: {outcome.config}")
print(f"  {outcome.tau:.0f} tok/s @ {outcome.power / 1e3:.2f} kW "
      f"feasible={outcome.feasible(tau_target, p_budget)}")

record = build_serving_record(
    "PYTHONPATH=src python examples/tune_serving.py",
    c_values, curve, rounds, batch_size=2, iters=iters,
    interval_s=interval_s, tau_target=tau_target, p_budget=p_budget,
    outcome=outcome, records=records, include_intervals=True,
)
with open("BENCH_serving.json", "w") as f:
    json.dump(record, f, indent=2, sort_keys=True)
    f.write("\n")
print("wrote BENCH_serving.json")
