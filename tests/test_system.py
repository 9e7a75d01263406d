"""End-to-end behaviour: the paper's full evaluation loop (Fig. 2) wired to
the framework — dry-run roofline terms → device simulator → CORAL vs
baselines — plus launcher entry points."""
import json
import os

import numpy as np
import pytest

from repro.core import run_coral, tpu_pod_space
from repro.core.baselines import alert, alert_online, oracle, preset
from repro.device import DeviceSimulator, RooflineTerms


@pytest.fixture(scope="module")
def artifact_terms():
    """Use a real dry-run artifact when present, else synthetic terms."""
    path = "experiments/dryrun/qwen2.5-3b__decode_32k__16x16.json"
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        r = rec["roofline"]
        return RooflineTerms(
            r["t_compute"], r["t_memory"], r["t_collective"], 2e-3,
            items_per_step=rec.get("global_batch", 128), n_chips=r["n_chips"],
        )
    from repro.device import synthetic_terms

    return synthetic_terms("memory_bound")


def test_full_loop_dual_constraint(artifact_terms):
    space = tpu_pod_space()
    dev0 = DeviceSimulator(space, artifact_terms, noise=0.0)
    orc_max = oracle(space, dev0, tau_target=0.0)
    tau_t = orc_max.tau * 0.6
    # anchor the budget to the max-power preset: τ-max configs can tie at
    # low power on memory/collective-bound workloads
    p_b = dev0.exact(space.preset("max_power"))[1] * 0.8
    orc = oracle(space, dev0, tau_t, p_b)
    assert orc.config is not None, "scenario must be satisfiable"

    feas = 0
    for seed in range(3):
        out, trace = run_coral(
            space, DeviceSimulator(space, artifact_terms, seed=seed),
            tau_t, p_b, iters=10, seed=seed,
        )
        assert len(trace.configs) == 10
        feas += out.feasible(tau_t, p_b)
    assert feas >= 2

    al = alert(space, DeviceSimulator(space, artifact_terms, seed=9), tau_t, p_b)
    alo = alert_online(space, DeviceSimulator(space, artifact_terms, seed=9),
                       tau_t, p_b)
    assert alo.config is None or alo.power <= p_b  # only feasible trials win
    mx = preset(space, DeviceSimulator(space, artifact_terms, seed=9), "max_power")
    # the paper's qualitative ordering
    assert al.tau >= orc.tau * 0.9  # ALERT chases throughput...
    assert not mx.feasible(tau_t, p_b) or mx.power > orc.power


def test_train_launcher_runs():
    from repro.launch.train import train

    _, losses = train("qwen2.5-3b", steps=6, batch=4, seq=32, reduced=True,
                      log_every=0)
    assert len(losses) == 6 and all(np.isfinite(losses))


def test_serve_launcher_runs():
    from repro.launch.serve import serve

    m, rt = serve("qwen2.5-3b", requests=2, prompt_len=8, new_tokens=4, batch=2)
    assert m["requests"] == 2
    assert sorted(r.output.size for r in rt.done) == [4, 4]


def test_input_specs_cover_all_pairs():
    from repro.configs.registry import REGISTRY
    from repro.configs.shapes import SHAPES
    from repro.configs.runtime import RunConfig
    from repro.launch.specs import input_specs

    for arch, cfg in REGISTRY.items():
        for shape in SHAPES.values():
            spec = input_specs(cfg, shape, RunConfig())
            assert spec, (arch, shape.name)
            if shape.kind == "decode":
                assert spec["tokens"].shape == (shape.global_batch, 1)
                assert "length" in spec["cache"]
