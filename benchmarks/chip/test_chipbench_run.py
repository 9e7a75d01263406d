"""The run command end to end on the CPU at a tiny size: it refuses to
measure without a TPU, and with the chip check skipped its correctness
check passes a sound run and fails each fault the served path can have."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import faults  # noqa: E402


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "qwen2.5-3b.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(cells.ROOT), timeout=300,
    )
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


def _tiny(name: str) -> cells.Cell:
    """The cell with its widths (by its program's own cut) and lengths cut
    to what a CPU test holds; the limit stays the cell's own."""
    cell = cells.resolve(name)
    config = cells.program_module(cell.config).tiny(cell.config)
    mix = dict(cell.traffic, prompt_lens=[16, 32][: len(cell.traffic["prompt_lens"])],
               batch=4, output_lens=dict(cell.traffic["output_lens"], lo=4, hi=12),
               lead_s=0.5)
    if mix["arrivals"] == "poisson":
        mix["rate_rps"] = 20.0
    return dataclasses.replace(cell, config=config, traffic=mix)


def _run(capsys, name, wrap=None):
    import run

    rc = run.run_cell(_tiny(name), seed=2**31 + 7, seconds=1.5, trace=False,
                      require_tpu=False, wrap=wrap)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("name", ["qwen2.5-3b.chat", "granite-8b.offline"])
def test_sound_run_is_correct(capsys, name):
    result = _run(capsys, name)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("max_gap", "logit_err"):
        check = result["checks"][name]
        assert check["value"] <= check["limit"]
    assert "setup_s" in result["metrics"] and "tokens_per_s" in result["metrics"]


@pytest.mark.parametrize("fault", [faults.AlteredToken, faults.StateUnchanged])
def test_fault_is_not_correct(capsys, fault):
    result = _run(capsys, "qwen2.5-3b.offline", wrap=fault)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("name", [w["name"] for w in cells.load_benchmark()["workloads"]])
def test_control_is_not_correct(name):
    """The plain reference with its matrices rounded to fp8 (the control,
    in the program's place at the same prompts and tokens) fails the
    cell's own limits; the program, served in bfloat16, passes them."""
    import run

    cell = _tiny(name)
    s = run.serve(cell, 2**31 + 11, 1.5)
    served, kept = run.served_tokens(cell, s.window, s.proxy, 2**31 + 11)
    limit = cell.limits["logit_err"]["limit"]
    program = run.readings(cell, s.engine.params, served, kept)
    control = run.readings(cell, s.engine.params, served, kept, quant="fp8")
    assert program["logits_compared"] > 0
    assert program["logit_err"] <= limit < control["logit_err"]
    assert program["max_gap"] <= cell.limits["max_gap"]["limit"]
