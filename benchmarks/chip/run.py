"""Run one cell of the benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's configuration with weights drawn from the seed, warms up
every shape its traffic uses, serves the traffic for ``--seconds`` through
``repro.serving.ServingRuntime``, checks the served tokens against the
plain float32 reference, and prints one JSON line last: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics (from a
profiler trace of the window's last seconds) with ``--trace 1``.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import cells  # noqa: E402

TRACE_S = 8.0  # seconds at the end of the window that a --trace 1 run profiles
KEEP_EVERY = 16  # one decode step in this many keeps its logits for the check


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def sample(tracked, n: int, seed: int):
    """The finished requests the reference checks: the longest, and the
    rest drawn from the seed."""
    done = [tr for tr in tracked if tr.request.output is not None]
    if not done:
        return []
    longest = max(done, key=lambda tr: (tr.planned.n_out, -tr.planned.rid))
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


@dataclasses.dataclass
class Served:
    """What one window left: the engine (its weights stay for the check),
    the proxy's dispatch records and the window's requests."""

    engine: object
    proxy: object
    window: object
    setup_s: float
    peak_bytes: int
    compiles: dict
    cache_dir: str
    phases: dict  # seconds from process start at the end of each set-up step


def serve(cell: cells.Cell, seed: int, seconds: float, trace_dir=None,
          wrap: Optional[Callable] = None, trace_s: float = TRACE_S) -> Served:
    """Build, warm up and serve one window of ``cell``'s traffic."""
    import jax

    from repro.compile_cache import use_compile_cache

    import drive
    import model

    phases = {"imported": time.monotonic() - T_START}
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _CompileCounter()
    config, mix = cell.config, cell.traffic
    vocab = int(config["vocab_size"])
    engine = model.build_engine(config, mix, seed)
    jax.block_until_ready(engine.params)
    phases["weights"] = time.monotonic() - T_START
    proxy = drive.StampingEngine(wrap(engine) if wrap is not None else engine,
                                 seed=seed)
    drive.warm_up(proxy, mix, vocab)
    proxy.keep_every = KEEP_EVERY
    phases["warmed_up"] = time.monotonic() - T_START
    compiles.mark("setup")
    window = drive.run_window(
        proxy, mix, seed, seconds, vocab, trace_dir=trace_dir,
        trace_s=min(trace_s, seconds), on_window_start=lambda: compiles.mark("lead"),
    )
    compiles.mark("window")
    setup_s = window.t0 - T_START
    peak = _peak_bytes(jax.devices()[: cell.chips])
    return Served(engine, proxy, window, setup_s, peak, compiles.counts, cache_dir,
                  phases)


def served_tokens(cell: cells.Cell, window, proxy, seed: int):
    """The sampled finished requests: their (prompt, served tokens), and
    the logits the program produced for them that the proxy kept."""
    checked = sample(window.tracked, int(cell.traffic["sample_requests"]), seed)
    served = [(tr.planned.prompt, np.asarray(tr.request.output)) for tr in checked]
    kept = [proxy.kept_for(p, t.size) for p, t in served]
    return served, kept


def failed_requests(window) -> int:
    """Finished requests that came back with another number of tokens
    than they asked for."""
    return sum(
        1 for tr in window.tracked
        if tr.request.output is not None and tr.request.output.size != tr.planned.n_out
    )


def readings(cell: cells.Cell, params, served, kept, quant=None) -> dict:
    """The numbers compared, against the plain reference: the widest gap
    by which a served token's reference logit lies below the reference's
    best, and the largest difference between a kept logit and the
    reference's. ``quant``: the control in the program's place."""
    import model

    ref = cells.reference_module(cell.config)
    out = ref.compare(params, cell.config, served, model.max_len(cell.traffic),
                      kept, quant=quant)
    gaps = np.concatenate([g for g, _ in out]) if out else np.zeros(0)
    errors = np.concatenate([e for _, e in out]) if out else np.zeros(0)
    return {
        "max_gap": float(gaps.max()) if gaps.size else float("inf"),
        "logit_err": float(errors.max()) if errors.size else float("inf"),
        "logits_compared": int(errors.size),
    }


def run_cell(
    cell: cells.Cell,
    seed: int,
    seconds: float,
    trace: bool,
    require_tpu: bool = True,
    wrap: Optional[Callable] = None,
    trace_s: float = TRACE_S,
) -> int:
    """One run of ``cell``; prints the result line and returns the exit
    code. ``wrap`` wraps the program's engine under the harness (tests
    plant faults with it)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        print(
            f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform!r} device(s)",
            file=sys.stderr,
        )
        return 2
    peaks = load_peaks(dev.device_kind) if dev.platform == "tpu" else None

    import reading
    import xtrace

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    s = serve(cell, seed, seconds, trace_dir, wrap, trace_s)
    window = s.window
    run = reading.RunRecord(cell.config, cell.traffic, window, s.proxy, peaks)
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            value = s.setup_s if m.name == "setup_s" else reading.END_TO_END[m.name](run)
            metrics[m.name] = {"value": value, "unit": m.unit}
    attempted = _attempted(run)
    failed = failed_requests(window)
    served, kept = served_tokens(cell, window, s.proxy, seed)

    # the program's state is gone (the runtime with its caches went with
    # the window); the reference runs beside the weights alone
    gc.collect()
    found = readings(cell, s.engine.params, served, kept)
    checks = {
        name: {"value": found[name], "limit": float(cell.limits[name]["limit"])}
        for name in ("max_gap", "logit_err")
    }
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = bool(served) and all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": s.peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        tr = xtrace.load(xtrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = tr
        run.trace_lo, run.trace_hi = tr.host_span("bench.window")
        run.trace_stop = window.trace_stop
        for m in cell.per_layer:
            value = cells.layer_reader(m.name).read(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
        device["busy_s"] = xtrace.busy_s(tr, run.trace_lo, run.trace_hi)
        device["window_s"] = run.trace_hi - run.trace_lo
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(tr, run.trace_lo, run.trace_hi),
            "idle_gaps": xtrace.gaps_by_host(tr, run.trace_lo, run.trace_hi),
        }
    result["metrics"] = metrics
    result["device"] = device
    summary = {
        "cell": cell.name, "seed": seed, "seconds": seconds,
        "requests_tracked": len(window.tracked), "sample": len(served),
        "logits_compared": found["logits_compared"],
        "sample_tokens": int(sum(t.size for _, t in served)),
        "generator_late_s": window.late_s, "compiles": s.compiles,
        "compile_cache": s.cache_dir, "setup_s": s.setup_s, "setup_phases": s.phases,
        "queue": window.queue,
    }
    print(f"run.py: {json.dumps(summary, sort_keys=True)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(result_line(result, checks))
    return 0


def result_line(result: dict, checks: dict) -> str:
    """The result as one JSON object, keys sorted, with ``checks`` (each
    number compared beside its limit) as the last key."""
    body = json.dumps(result, sort_keys=True)
    return body[:-1] + ', "checks": ' + json.dumps(checks, sort_keys=True) + "}"


def _attempted(run) -> int:
    """Requests due in the window (open loop), or that received a token in
    it (closed-loop backlog)."""
    if run.mix["arrivals"] == "backlog":
        return len({id(tr) for tr, _, _ in run.window_tokens()})
    return len(run.due_in_window())


def _peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks)


class _CompileCounter:
    """Counts, by phase of the run, the programs JAX had to compile
    (``programs``) and those the persistent cache did not hold (``misses``)."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "programs",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax.monitoring

        self.counts: dict = {}
        self._n = {"programs": 0, "misses": 0}
        self._marked = dict(self._n)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event in self._EVENTS:
            self._n[self._EVENTS[event]] += 1

    def mark(self, phase: str) -> None:
        self.counts[phase] = {k: self._n[k] - self._marked[k] for k in self._n}
        self._marked = dict(self._n)


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.resolve(args.workload)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
