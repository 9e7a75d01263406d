"""Micro-benchmarks for the Pallas kernels (interpret mode on CPU — the
derived column reports correctness vs oracle, not TPU speed) plus the
vectorized-analytics suite. Two machine-readable records come out:
BENCH_kernels.json (per-kernel correctness + interpret-mode timing; the
regression gate bounds the *error*, never the CPU wall time) and
BENCH_analytics.json (loop-vs-batched speedups)."""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import begin, emit_json, quick, row, timeit
from repro.core.dcov import (
    dcor,
    dcor_all,
    dcor_all_cols,
    dcor_numpy,
    dcor_state_corr,
    dcor_state_from_window,
    dcor_state_push,
)
from repro.kernels.dcov import dcor_all_pallas, dcor_pallas, dcor_ref
from repro.kernels.dcov.dcov import default_interpret
from repro.kernels.flash_attention import attention_ref, flash_attention_bhsd
from repro.kernels.ssd_scan import ssd, ssd_ref

ANALYTICS_JSON = Path(__file__).resolve().parent.parent / "BENCH_analytics.json"
KERNELS_JSON = Path(__file__).resolve().parent.parent / "BENCH_kernels.json"
# CI smoke: fewer timing iterations (QUICK=0/false/empty means full run)
QUICK = quick()


def bench_dcov_kernel(record: dict | None = None):
    rng = np.random.default_rng(0)
    n = 512
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    y = jnp.asarray(np.asarray(x) ** 2 + rng.normal(size=n) * 0.1, jnp.float32)
    us_pallas = timeit(lambda: dcor_pallas(x, y, block=128).block_until_ready())
    us_ref = timeit(lambda: dcor_ref(x, y).block_until_ready())
    us_core = timeit(lambda: dcor(x, y).block_until_ready())
    err = abs(float(dcor_pallas(x, y)) - float(dcor_ref(x, y)))
    row("dcov_pallas_n512", us_pallas, f"err_vs_ref={err:.1e}")
    row("dcov_ref_n512", us_ref, "materialized n×n oracle")
    row("dcov_core_jnp_n512", us_core, "model-side jnp implementation")
    if record is not None:
        record["dcov_pallas_n512"] = {"us": us_pallas, "err_vs_ref": err}
    # ORACLE-scale: beyond one VMEM tile the auto-blocked kernel (16×16
    # grid of 128-tiles here) must stay correct, not degrade to an
    # oversized single tile. Timing is interpret-mode (correctness gate
    # only); one rep keeps the 256-step grid walk affordable in CI.
    n2 = 2048
    x2 = jnp.asarray(rng.normal(size=n2), jnp.float32)
    y2 = jnp.asarray(np.asarray(x2) ** 2 + rng.normal(size=n2) * 0.1, jnp.float32)
    us2 = timeit(lambda: dcor_pallas(x2, y2).block_until_ready(), iters=1)
    err2 = abs(float(dcor_pallas(x2, y2)) - float(dcor_ref(x2, y2)))
    row("dcov_pallas_n2048", us2, f"err_vs_ref={err2:.1e} (auto block)")
    if record is not None:
        record["dcov_pallas_n2048"] = {"us": us2, "err_vs_ref": err2}


def bench_flash_attention_kernel(record: dict | None = None):
    rng = np.random.default_rng(1)
    b, hq, hkv, s, d = 1, 4, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    us = timeit(
        lambda: flash_attention_bhsd(q, k, v, block_q=64, block_k=64)
        .block_until_ready(),
        iters=2,
    )
    err = float(
        jnp.max(
            jnp.abs(
                flash_attention_bhsd(q, k, v, block_q=64, block_k=64)
                - attention_ref(q, k, v)
            )
        )
    )
    row("flash_attention_s256", us, f"err_vs_ref={err:.1e} (interpret mode)")
    if record is not None:
        record["flash_attention_s256"] = {"us": us, "err_vs_ref": err}


def bench_ssd_kernel(record: dict | None = None):
    rng = np.random.default_rng(2)
    b, s, nh, hd, n, chunk = 1, 256, 2, 32, 16, 32
    x = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, s, nh)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2, size=(nh,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    us = timeit(
        lambda: ssd(x, dt, A, Bm, Cm, chunk=chunk)[0].block_until_ready(), iters=2
    )
    y1, s1 = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    err = float(jnp.max(jnp.abs(y1 - y2)))
    row("ssd_scan_s256", us, f"err_vs_ref={err:.1e} (interpret mode)")
    if record is not None:
        record["ssd_scan_s256"] = {"us": us, "err_vs_ref": err}


def bench_incremental_dcor(record: dict | None = None):
    """Fleet-path windowed dCor: O(W·C) rank-1 ring update + O(C²) readout
    per observation vs the O(W²·C) full recompute (``dcor_all_cols``).
    Both sides are jitted jnp on the same backend, so the speedup ratio is
    machine-stable and gated by check_regression like the other ratios."""
    w, d, m = 64, 5, 2
    c = d + m
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(w + 40, c)), jnp.float32)
    n32 = jnp.int32(w)

    full = jax.jit(lambda cols: dcor_all_cols(cols, n32, d))
    push = jax.jit(lambda st, new, slot: dcor_state_push(st, new, slot, n32))
    corr = jax.jit(lambda st: dcor_state_corr(st, n32, d))

    st = {k: v.block_until_ready() for k, v in
          dcor_state_from_window(rows[:w], n32).items()}
    new_row, slot = rows[w], jnp.int32(0)

    def per_step_incremental():
        return corr(push(st, new_row, slot)).block_until_ready()

    def per_step_full():
        return full(rows[:w]).block_until_ready()

    iters = 3 if QUICK else 30
    us_full = timeit(per_step_full, iters=iters)
    us_incr = timeit(per_step_incremental, iters=iters)
    speedup = us_full / max(us_incr, 1e-9)

    # Correctness over a 40-push ring replay (wrap-around included):
    # incremental readout vs a full recompute of the reassembled window.
    win = np.asarray(rows[:w]).copy()
    err = 0.0
    for t in range(40):
        s = (w + t) % w
        st = push(st, rows[w + t], jnp.int32(s))
        win[s] = np.asarray(rows[w + t])
        err = max(err, float(np.abs(
            np.asarray(corr(st)) - np.asarray(full(jnp.asarray(win)))
        ).max()))

    row(
        f"dcor_incremental_W{w}_D{d}",
        us_incr,
        f"full={us_full:.0f}us speedup={speedup:.1f}x err={err:.1e}",
    )
    if record is not None:
        record[f"dcor_incremental_W{w}_D{d}"] = {
            "full_us": us_full,
            "incremental_us": us_incr,
            "speedup": speedup,
            "err_vs_ref": err,
        }


def bench_coral_iteration_overhead():
    """Per-iteration optimizer cost (dCor over the sliding window) — the
    paper's 'lightweight online search' claim."""
    from repro.core import CORAL, tpu_pod_space

    space = tpu_pod_space()
    opt = CORAL(space, tau_target=10.0, p_budget=100.0)
    rng = np.random.default_rng(0)
    for i in range(10):
        cfg = space.random(rng)
        opt.observe(cfg, 10 + rng.random(), 50 + rng.random())
    us = timeit(lambda: opt.correlations(), iters=5)
    row("coral_correlation_step", us, "5 dims × 2 metrics, window=10")
    us2 = timeit(lambda: opt.propose(), iters=5)
    row("coral_propose_step", us2, "Alg-2 + prohibited-set escape")


# ---------------------------------------------------------------------------
# Vectorized-analytics suite — loop-vs-batched timings, recorded to
# BENCH_analytics.json so later PRs can track the perf trajectory.
# ---------------------------------------------------------------------------


def bench_batched_dcor(record: dict | None = None):
    """CORAL's correlation step: 2×D per-pair dcor calls vs one dcor_all."""
    w, d, m = 10, 5, 2
    rng = np.random.default_rng(0)
    settings = rng.normal(size=(w, d)).astype(np.float32)
    metrics = rng.normal(size=(w, m)).astype(np.float32)

    def loop():
        out = np.zeros((d, m), np.float32)
        for i in range(d):
            for j in range(m):
                out[i, j] = dcor_numpy(metrics[:, j], settings[:, i])
        return out

    def batched():
        return np.asarray(
            dcor_all(jnp.asarray(settings), jnp.asarray(metrics), np.int32(w))
        )

    iters = 3 if QUICK else 20
    us_loop = timeit(loop, iters=iters)
    us_batched = timeit(batched, iters=iters)
    err = float(np.abs(loop() - batched()).max())
    speedup = us_loop / max(us_batched, 1e-9)
    row(
        f"dcor_window_W{w}_D{d}",
        us_batched,
        f"loop={us_loop:.0f}us speedup={speedup:.1f}x err={err:.1e}",
    )
    if record is not None:
        record[f"dcor_window_W{w}_D{d}"] = {
            "loop_us": us_loop,
            "batched_us": us_batched,
            "speedup": speedup,
            "max_abs_err": err,
        }


def bench_batched_dcor_pallas(record: dict | None = None):
    """ORACLE-scale batched Gram kernel vs C·(C−1)/2 + C pairwise launches."""
    n, d, m = 512, 5, 2
    rng = np.random.default_rng(1)
    settings = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    metrics = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)

    def pairwise():
        return np.array(
            [
                [float(dcor_pallas(metrics[:, j], settings[:, i], block=128))
                 for j in range(m)]
                for i in range(d)
            ]
        )

    def batched():
        return np.asarray(dcor_all_pallas(settings, metrics, block=128))

    iters = 1 if QUICK else 3
    us_pair = timeit(pairwise, iters=iters)
    us_batched = timeit(batched, iters=iters)
    err = float(np.abs(pairwise() - batched()).max())
    speedup = us_pair / max(us_batched, 1e-9)
    row(
        f"dcor_all_pallas_n{n}",
        us_batched,
        f"pairwise={us_pair:.0f}us speedup={speedup:.1f}x err={err:.1e} "
        "(interpret mode)",
    )
    if record is not None:
        record[f"dcor_all_pallas_n{n}_D{d}_M{m}"] = {
            "pairwise_us": us_pair,
            "batched_us": us_batched,
            "speedup": speedup,
            "max_abs_err": err,
        }


def bench_oracle_vectorized(record: dict | None = None):
    """Exhaustive search on the 2160-config Xavier-NX space: scalar Python
    sweep vs one array-based evaluation."""
    from repro.core import jetson_like_space
    from repro.core.baselines import oracle, oracle_scalar
    from repro.device import jetson_like_simulator

    space = jetson_like_space("xavier_nx")
    dev = jetson_like_simulator(space, 1.0, noise=0.0)
    tau_t = 30.0

    iters = 1 if QUICK else 3
    us_scalar = timeit(lambda: oracle_scalar(space, dev, tau_t), iters=iters)
    us_vec = timeit(lambda: oracle(space, dev, tau_t), iters=iters)
    same = oracle(space, dev, tau_t).config == oracle_scalar(space, dev, tau_t).config
    speedup = us_scalar / max(us_vec, 1e-9)
    row(
        f"oracle_xavier_nx_{space.size()}",
        us_vec,
        f"scalar={us_scalar:.0f}us speedup={speedup:.1f}x same_config={same}",
    )
    if record is not None:
        record[f"oracle_xavier_nx_{space.size()}"] = {
            "scalar_us": us_scalar,
            "vectorized_us": us_vec,
            "speedup": speedup,
            "same_config": bool(same),
        }


def bench_analytics_suite():
    """Run the analytics benches and emit BENCH_analytics.json."""
    record: dict = {}
    bench_batched_dcor(record)
    bench_batched_dcor_pallas(record)
    bench_oracle_vectorized(record)
    payload = {
        "regenerate": "PYTHONPATH=src python -m benchmarks.kernels_bench",
        "results": record,
    }
    emit_json(ANALYTICS_JSON, payload)
    row("analytics_json", 0.0, f"wrote {ANALYTICS_JSON.name}")


def bench_kernels_suite():
    """Run the Pallas-kernel benches and emit BENCH_kernels.json."""
    record: dict = {}
    bench_dcov_kernel(record)
    bench_flash_attention_kernel(record)
    bench_ssd_kernel(record)
    bench_incremental_dcor(record)
    bench_coral_iteration_overhead()
    payload = {
        "regenerate": "PYTHONPATH=src python -m benchmarks.kernels_bench",
        # Timing provenance: interpret-mode CPU numbers (e.g. the ~100ms
        # dcov_pallas_n2048 walk) must never be compared against compiled
        # accelerator numbers — check_regression refuses records whose
        # backend/interpret provenance differs from the baseline's.
        "backend": jax.default_backend(),
        "pallas_interpret": bool(default_interpret()),
        # timing-depth provenance: QUICK runs use 3 timing iterations
        "quick": QUICK,
        "results": record,
    }
    emit_json(KERNELS_JSON, payload)
    row("kernels_json", 0.0, f"wrote {KERNELS_JSON.name}")


if __name__ == "__main__":
    begin()
    bench_kernels_suite()
    bench_analytics_suite()
