"""Chip smoke test: the CORAL serving loop at full qwen2.5-3b width on one TPU.

    python chip_smoke.py

Every phase runs in this one process (a chip belongs to one process at a
time) and prints one line:

  kernels    dcov Gram at n=2048 with its default block and flash attention
             at qwen2.5-3b prefill widths, compiled by Mosaic
             (``tpu_custom_call`` in the program), against their ref.py
             run in float32 on the host CPU
  serve      qwen2.5-3b at its published widths and depth (36 layers, bf16
             weights from a seed) through repro.launch.serve →
             ServingEngine → ServingRuntime; every request returns its
             tokens and prefill runs the Pallas flash kernel
  reference  the same weights and prompts through the XLA attention with
             float32 compute at "highest" matmul precision: last-position
             logits of prefill and of DECODE_STEPS teacher-forced decode
             steps, held to LOGIT_TOL and TOP1_TOL
  coral      ServingController closed loop over a seeded bursty trace on
             that runtime (power p is modelled, DVFS is pacing-emulated)
  engine     one committed static cell through the compiled and the scalar
             episode engines, per seed and whole, against BENCH_matrix.json
  fleet      run_fleet(64) against the fleet floors of check_regression,
             and the committed 1024-twin fleet against BENCH_fleet.json

The last line is the device JSON. Without a TPU, or when any phase
fails, the script exits non-zero and prints no JSON line. Tokens/s
printed here are informational, not measurements.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "qwen2.5-3b"
BATCH, PROMPT, NEW_TOKENS, REQUESTS, CONCURRENCY = 4, 128, 32, 8, 2
DECODE_STEPS = 4
# bf16 serving vs the float32 reference, same bf16 weights: the largest
# last-position logit error may be this fraction of the reference's
# largest |logit| ...
LOGIT_TOL = 0.05
# ... every row whose reference top-1 leads its runner-up by more than
# twice the largest error must pick the same token, and at least this
# share of all rows must
TOP1_TOL = 0.75
DCOV_TOL = 1e-4  # relative to the reference Gram's largest entry
FLASH_TOL = 2.0**-6  # |error| / max(|ref|, 1): two bf16 ulps
STATIC_CELL = ("edge-xavier-nx", "qwen2.5-3b", "prefill_steady", "strict_dual")
CORAL_INTERVALS, CORAL_INTERVAL_S, CORAL_NEW_TOKENS = 4, 2.0, 16
FLEET_TWINS = 64


def _check(ok: bool, what) -> None:
    """Fail the phase (a plain raise: ``assert`` vanishes under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase_kernels() -> dict:
    """Each kernel against its ref.py evaluated in float32 on the host
    CPU backend: the TPU's own float32 matmuls are not full precision
    (ref.py's 4M-term Gram einsum on the chip is off by ~1e-4)."""
    from repro.kernels.dcov.dcov import dcov_gram_pallas
    from repro.kernels.dcov.ref import dcov_gram_ref
    from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
    from repro.kernels.flash_attention.ref import attention_ref

    host = jax.devices("cpu")[0]

    def on_host(fn, *args):
        with jax.default_device(host):
            return np.asarray(fn(*(jax.device_put(np.asarray(a), host) for a in args)))

    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.normal(size=(2048, 6)), jnp.float32)
    dcov = jax.jit(lambda x: dcov_gram_pallas(x)).lower(cols).compile()
    _check("tpu_custom_call" in dcov.as_text(), "dcov kernel not compiled")
    got = np.asarray(dcov(cols))
    want = on_host(dcov_gram_ref, cols)
    dcov_err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    b, hq, hkv, s, d = BATCH, 16, 2, 512, 128
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
        for h in (hq, hkv, hkv)
    )
    flash = jax.jit(lambda *a: flash_attention_bhsd(*a)).lower(q, k, v).compile()
    _check("tpu_custom_call" in flash.as_text(), "flash kernel not compiled")
    got = np.asarray(flash(q, k, v), np.float32)
    want = on_host(attention_ref, q, k, v).astype(np.float32)
    flash_err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
    out = {"dcov_n2048_rel_err": dcov_err, "flash_s512_rel_err": flash_err}
    _check(dcov_err <= DCOV_TOL, out)
    _check(flash_err <= FLASH_TOL, out)
    return out


def phase_serve(state: dict) -> dict:
    from repro.launch.serve import serve

    metrics, rt = serve(
        ARCH, REQUESTS, PROMPT, NEW_TOKENS, BATCH, CONCURRENCY,
        reduced=False, param_dtype="bfloat16",
    )
    state["rt"], state["metrics"] = rt, metrics
    eng = rt.engine
    cfg = eng.ctx.cfg
    lowered = eng._prefill.lower(
        eng.params, {"tokens": jnp.zeros((BATCH, PROMPT), jnp.int32)}
    )
    out = {
        "layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "vocab": cfg.vocab,
        "param_dtype": eng.ctx.rcfg.param_dtype,
        "params": int(sum(x.size for x in jax.tree.leaves(eng.params))),
        "requests_done": len(rt.done),
        "tokens": int(sum(r.output.size for r in rt.done if r.output is not None)),
        "pallas_prefill": "tpu_custom_call" in lowered.as_text(),
        "tok_s_informational": metrics["throughput_tok_s"],
        "peak_bytes_in_use": _peak_bytes(),
    }
    _check(out["requests_done"] == REQUESTS, out)
    _check(out["tokens"] == REQUESTS * NEW_TOKENS, out)
    _check(out["pallas_prefill"], "serving prefill took the XLA attention")
    return out


def phase_reference(state: dict) -> dict:
    from repro.models.transformer import ApplyCtx
    from repro.serving.engine import make_prefill_step, make_serve_step

    eng = state["rt"].engine
    rcfg = dataclasses.replace(
        eng.ctx.rcfg, use_pallas=False, compute_dtype="float32"
    )
    ref_ctx = ApplyCtx(eng.ctx.cfg, rcfg, None)
    prompts = np.stack(
        [r.prompt for r in sorted(state["rt"].done, key=lambda r: r.rid)[:BATCH]]
    )
    ref_prefill = jax.jit(make_prefill_step(ref_ctx, capacity=eng.max_len))
    ref_decode = jax.jit(make_serve_step(ref_ctx))

    cache, logits = eng.prefill(prompts)
    with jax.default_matmul_precision("highest"):
        rcache, rlogits = ref_prefill(eng.params, {"tokens": jnp.asarray(prompts)})
    pairs = [(logits, rlogits)]
    for _ in range(DECODE_STEPS):
        # teacher-forced: both sides consume the reference's greedy token
        tok = jnp.argmax(rlogits[:, -1:], axis=-1).astype(jnp.int32)
        cache, logits = eng.decode(cache, tok)
        with jax.default_matmul_precision("highest"):
            rcache, rlogits = ref_decode(eng.params, rcache, tok)
        pairs.append((logits, rlogits))

    got = np.concatenate([np.asarray(a[:, -1], np.float32) for a, _ in pairs])
    want = np.concatenate([np.asarray(b[:, -1], np.float32) for _, b in pairs])
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    top2 = np.sort(want, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    agree = got.argmax(-1) == want.argmax(-1)
    out = {
        "rows": int(agree.size),
        "max_abs_err": err,
        "ref_max_abs_logit": scale,
        "rel_err": err / scale,
        "top1_agreement": float(agree.mean()),
        "top1_flips_beyond_margin": int(np.sum(~agree & (margin > 2 * err))),
        "tolerance": {"rel_err": LOGIT_TOL, "top1": TOP1_TOL},
        "peak_bytes_in_use": _peak_bytes(),
    }
    _check(out["rel_err"] <= LOGIT_TOL, out)
    _check(out["top1_agreement"] >= TOP1_TOL, out)
    _check(out["top1_flips_beyond_margin"] == 0, out)
    return out


def phase_coral(state: dict) -> dict:
    from repro.core import tpu_pod_space
    from repro.device.measure import analytic_scale_and_power
    from repro.serving import (
        ServingController,
        ServingRuntime,
        measure_runtime_throughput,
        workload,
    )

    eng = state["rt"].engine
    # the trace rate scales with a warm drain (shapes compiled by serve)
    cap = measure_runtime_throughput(
        eng, CONCURRENCY, prompt_len=PROMPT, new_tokens=CORAL_NEW_TOKENS,
        groups=4, vocab=eng.ctx.cfg.vocab, warmup=False,
    )
    space = tpu_pod_space()
    trace = workload.bursty_poisson(
        rate=0.6 * cap / CORAL_NEW_TOKENS,
        duration_s=CORAL_INTERVALS * CORAL_INTERVAL_S + 2.0,
        prompt_lens=PROMPT, new_tokens=CORAL_NEW_TOKENS,
        vocab=eng.ctx.cfg.vocab, seed=1,
    )
    tau_target = 0.45 * cap
    p_budget = 0.8 * analytic_scale_and_power(
        space.names, space.preset("max_power")
    )[1]
    runtime = ServingRuntime(eng, concurrency=1)
    ctrl = ServingController(
        runtime, space, trace, tau_target=tau_target, p_budget=p_budget,
        interval_s=CORAL_INTERVAL_S,
    )
    intervals = []
    for k in range(CORAL_INTERVALS):
        rec = ctrl.control_step()
        intervals.append({
            "config": dict(zip(space.names, rec.config)),
            "tau_tok_s": rec.tau,
            "p_modelled_w": rec.power,
            "dvfs_pacing_scale": runtime.rate_scale,
            "requests_done": rec.requests_done,
            "queue_depth": rec.queue_depth,
        })
        print(f"  coral interval {k}: {json.dumps(intervals[-1])}", flush=True)
    res = ctrl.opt.result()
    out = {
        "intervals": len(intervals),
        "warm_tok_s_informational": cap,
        "tau_target": tau_target,
        "p_budget_modelled_w": p_budget,
        "pick": list(res.config) if res is not None else None,
        "dvfs": "pacing-emulated",
        "power": "modelled",
    }
    _check(len(intervals) >= 3, "fewer than 3 control intervals")
    _check(
        all(np.isfinite(i["tau_tok_s"]) and i["tau_tok_s"] >= 0 for i in intervals),
        out,
    )
    return out


def phase_engine() -> dict:
    from repro.experiments.matrix import run_static_cell
    from repro.experiments.scenarios import Cell

    cell = Cell(*STATIC_CELL)
    committed = next(
        c
        for c in json.loads((ROOT / "BENCH_matrix.json").read_text())["cells"]
        if (c["device"], c["model"], c["workload"], c["regime"]) == STATIC_CELL
    )
    per_seed = {}
    for seed in (0, 1, 2):
        a = run_static_cell(cell, seeds=(seed,), engine="compiled")["coral"]
        b = run_static_cell(cell, seeds=(seed,), engine="scalar")["coral"]
        per_seed[seed] = {"compiled": a["config"], "scalar": b["config"],
                          "equal": a == b}
    whole = run_static_cell(cell, engine="compiled")
    scalar = run_static_cell(cell, engine="scalar")
    out = {
        "cell": "/".join(STATIC_CELL),
        "per_seed": per_seed,
        "compiled_equals_scalar": whole == scalar,
        "compiled_equals_record": whole == committed,
        "coral_score": whole["coral"]["score"],
        "record_score": committed["coral"]["score"],
        "coral_config": whole["coral"]["config"],
        "record_config": committed["coral"]["config"],
    }
    _check(all(v["equal"] for v in per_seed.values()), out)
    _check(out["compiled_equals_scalar"], out)
    _check(out["compiled_equals_record"], out)
    return out


def phase_fleet() -> dict:
    from benchmarks.check_regression import (
        FLEET_FEASIBLE_FLOOR,
        FLEET_WARM_GAIN_FLOOR,
    )
    from repro.experiments.fleet import run_fleet

    res = run_fleet(FLEET_TWINS)["results"]
    # the committed fleet is deterministic: same seed, same results block
    committed = json.loads((ROOT / "BENCH_fleet.json").read_text())["results"]
    full = run_fleet(committed["n_twins"], seed=committed["seed"])["results"]
    out = {
        "n_twins": res["n_twins"],
        "feasible_rate": res["feasible_rate"],
        "warm_gain": res["warm_gain"],
        "floors": [FLEET_FEASIBLE_FLOOR, FLEET_WARM_GAIN_FLOOR],
        f"fleet{committed['n_twins']}_equals_record": full == committed,
    }
    _check(res["feasible_rate"] >= FLEET_FEASIBLE_FLOOR, out)
    _check(res["warm_gain"] is not None and res["warm_gain"] >= FLEET_WARM_GAIN_FLOOR, out)
    _check(full == committed, out)
    return out


def _cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU — jax.devices()[0].platform is "
            f"{dev.platform!r}; this script only runs on a TPU",
            file=sys.stderr,
        )
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache_before = _cache_entries(cache_dir)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[device] {json.dumps(device)}", flush=True)

    state: dict = {}
    phases = [
        ("serve", lambda: phase_serve(state)),
        ("reference", lambda: phase_reference(state)),
        ("coral", lambda: phase_coral(state)),
        ("kernels", phase_kernels),
        ("engine", phase_engine),
        ("fleet", phase_fleet),
    ]
    failed = []
    for name, run in phases:
        if name in ("reference", "coral") and "rt" not in state:
            failed.append(name)
            print(f"[{name}] FAIL: serve phase did not finish", flush=True)
            continue
        if name == "kernels":
            state.clear()  # free the served model before the next phases
            gc.collect()
        t0 = time.perf_counter()
        try:
            out = run()
            out["wall_s"] = round(time.perf_counter() - t0, 3)
            print(f"[{name}] ok {json.dumps(out, default=str)}", flush=True)
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            failed.append(name)
            print(f"[{name}] FAIL: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    print(
        f"[cache] {json.dumps({'dir': cache_dir, 'entries_before': cache_before, 'entries_after': _cache_entries(cache_dir)})}",
        flush=True,
    )
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
