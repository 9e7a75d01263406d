"""The program seam: a configuration file names the program module that
builds the system under test (``programs/<name>.py``), and the dense
default builds the existing configurations exactly as before the seam.

A non-dense configuration is added with new files and entries only: a
program module, a configuration, a plain reference, a traffic mix and
limits, run on the CPU through ``run.run_cell`` (end-to-end metrics and
the correctness check) with no other file of the harness changed.
"""
import copy
import dataclasses
import hashlib
import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import model  # noqa: E402

CONFIGS = ["qwen2.5-3b", "granite-8b"]


def _config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _checksum(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of every leaf's path, dtype and bytes, in tree order, of the
# bfloat16 weights at the dense program's CPU cut from seed 2**31 + 3:
# recorded from the harness as it was before the program seam, when
# model.py drew every leaf by its own fixed rules
PARENT_WEIGHTS = {
    "qwen2.5-3b": "933dc1729b33541000bb39c01820151c7ef10d7d22a949962568c711d4a77be5",
    "granite-8b": "5db3af3a889d5031c5b2a714cbb631a4040fadea90040facc7782bab82b2044b",
}


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_those_before_the_seam(name):
    from repro.models.transformer import abstract_model_params

    config = cells.program_module(_config(name)).tiny(_config(name))
    cfg = model.program_config(config)
    abstract = abstract_model_params(cfg, model.run_config(config))
    assert _checksum(model.make_weights(config, abstract, 2**31 + 3)) == PARENT_WEIGHTS[name]


def _parent_model_config(name: str):
    """The ``ModelConfig`` the harness built for each full configuration
    file before the program seam, field by field."""
    from repro.configs.base import ModelConfig

    if name == "qwen2.5-3b":
        return ModelConfig(
            name="qwen2", arch_type="dense", n_layers=36, d_model=2048, n_heads=16,
            n_kv_heads=2, d_ff=11008, vocab=151936, qkv_bias=True,
            rope_theta=1000000.0, norm_eps=1e-06, tie_embeddings=True,
            source="https://huggingface.co/Qwen/Qwen2.5-3B")
    return ModelConfig(
        name="llama", arch_type="dense", n_layers=18, d_model=4096, n_heads=32,
        n_kv_heads=8, d_ff=14336, vocab=49152, qkv_bias=False,
        rope_theta=10000000.0, norm_eps=1e-05, tie_embeddings=False,
        source="https://arxiv.org/abs/2405.04324")


@pytest.mark.parametrize("name", CONFIGS)
def test_model_config_is_that_before_the_seam(name):
    got = dataclasses.asdict(model.program_config(_config(name)))
    assert got == dataclasses.asdict(_parent_model_config(name))


def test_dense_run_config_is_the_serving_default():
    from repro.configs.runtime import serving_config

    assert model.run_config(_config("qwen2.5-3b")) == serving_config(param_dtype="bfloat16")


# ---------------------------------------------------------------------------
# A non-dense configuration added with new files only

SSM_PROGRAM = """\"\"\"Mamba-2 (SSD) through the program's attention-free model.\"\"\"
import jax
import jax.numpy as jnp
import numpy as np

DT_MIN, DT_MAX = 1e-3, 0.1  # range of the initial step size, as in Mamba-2
RUN_CONFIG = {"use_pallas": True}  # the SSD scan through its Pallas kernel on every backend


def program_config(config):
    from repro.configs.base import ModelConfig, SSMConfig

    return ModelConfig(
        name=config["model_type"], arch_type="ssm",
        n_layers=int(config["num_hidden_layers"]), d_model=int(config["hidden_size"]),
        n_heads=0, n_kv_heads=0, d_ff=0, vocab=int(config["vocab_size"]),
        ssm=SSMConfig(d_state=int(config["state_size"]), headdim=int(config["head_dim"]),
                      expand=int(config["expand"]), chunk_size=int(config["chunk_size"]),
                      d_conv=int(config["conv_kernel"])),
        rope_type="none", norm_eps=float(config["layer_norm_epsilon"]),
        tie_embeddings=bool(config["tie_word_embeddings"]), source=config["source"])


def leaf_init(name, shape, z):
    u = jax.scipy.stats.norm.cdf(z)  # uniform on (0, 1)
    if name == "A_log":  # A = -exp(A_log) in [-16, -1]
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":  # softplus(dt_bias) log-uniform in [DT_MIN, DT_MAX]
        dt = jnp.exp(np.log(DT_MIN) + u * (np.log(DT_MAX) - np.log(DT_MIN)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("D", "ln1", "final_norm", "norm_w"):
        return 1.0 + 0.1 * z
    if name == "conv_b":
        return 0.1 * z
    if name == "embed":
        return z / np.sqrt(shape[-1])
    return None


def tiny(config):
    return dict(config, hidden_size=64, num_hidden_layers=2, state_size=16,
                head_dim=16, chunk_size=8, vocab_size=512)
"""

SSM_REFERENCE = """\"\"\"Plain float32 reference of a Mamba-2 stack with a tied head: each
mixer as its recurrence, one position at a time, every product at
HIGHEST precision. It imports nothing of the program.\"\"\"
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mixer(p, x, config):
    \"\"\"One Mamba-2 mixer over the normed rows x (L, d).\"\"\"
    n, hd = int(config["state_size"]), int(config["head_dim"])
    di = int(config["expand"]) * int(config["hidden_size"])
    proj = _mm("ld,de->le", x, p["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], proj[:, 2 * di + 2 * n:]
    k = p["conv_w"].shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + x.shape[0]] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[:, :di].reshape(x.shape[0], di // hd, hd)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(state, t):  # state (heads, hd, n)
        x_t, b_t, c_t, dt_t = t
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return state, _mm("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((di // hd, hd, n)), (xs, b, c, dt))
    y = (y + xs * p["D"][:, None]).reshape(x.shape[0], di)
    eps = float(config["layer_norm_epsilon"])
    return _mm("le,ed->ld", _rms(y * jax.nn.silu(z), p["norm_w"], eps), p["out_proj"])


def hidden(weights, config, tokens):
    \"\"\"Final-norm hidden states (L, d) of one row of tokens.\"\"\"
    w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), weights)
    eps = float(config["layer_norm_epsilon"])
    h = w["embed"][tokens]
    for i in range(int(config["num_hidden_layers"])):
        lp = jax.tree.map(lambda a: a[i], w["layers"])
        h = h + _mixer(lp["ssm"], _rms(h, lp["ln1"], eps), config)
    return _rms(h, w["final_norm"], eps)


def compare(weights, config, served, length, kept, quant=None):
    \"\"\"Per (prompt, served tokens): the gap of each served token below
    the reference's best, and the largest logit error at each kept index.\"\"\"
    if quant is not None:
        raise ValueError("no control for this reference")
    head = jnp.asarray(weights["embed"], jnp.float32).T
    out = []
    for (prompt, toks), (index, logits) in zip(served, kept):
        seq = np.concatenate([prompt, toks[:-1]])
        ref = np.asarray(_mm("ld,dv->lv", hidden(weights, config, seq), head))
        ref = ref[prompt.size - 1:]
        gaps = ref.max(-1) - ref[np.arange(toks.size), toks]
        errors = np.abs(logits - ref[index]).max(-1) if index.size else np.zeros(0)
        out.append((gaps, errors))
    return out
"""

# mamba2-2.7b's block at a reduced size
SSM_CONFIG = {
    "source": "https://arxiv.org/abs/2405.21060",
    "program": "mamba2_ssd",
    "reference": "mamba2_ssd",
    "model_type": "mamba2",
    "hidden_size": 256, "num_hidden_layers": 4, "state_size": 32, "head_dim": 32,
    "expand": 2, "chunk_size": 16, "conv_kernel": 4, "n_groups": 1,
    "vocab_size": 1024, "layer_norm_epsilon": 1e-05, "tie_word_embeddings": True,
    "changed_from_source": {"hidden_size": 2560, "num_hidden_layers": 64,
                            "state_size": 128, "head_dim": 64, "chunk_size": 256,
                            "vocab_size": 50280},
}

SSM_MIX = {"arrivals": "backlog", "backlog_groups": 2, "prompt_lens": [24],
           "output_lens": {"dist": "log_uniform", "lo": 8, "hi": 24},
           "batch": 4, "slots": 2, "lead_s": 0.5, "sample_requests": 4}

# the program reads a logit error of 0.039 and a gap of 0 here on the
# CPU; the reference with its D term scaled by 0.9 reads 0.185
SSM_LIMITS = {"max_gap": {"limit": 1.0}, "logit_err": {"limit": 0.1}}

SSM_CELL = "mamba2-small.short_backlog"
SSM_ADDED = {"configs/mamba2-small.json", "limits/mamba2-small.short_backlog.json",
             "programs/mamba2_ssd.py", "references/mamba2_ssd.py",
             "traffic/short_backlog.json"}


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def harness_root(tmp_path, monkeypatch):
    """A copy of the harness's data (BENCHMARK.json and every directory of
    named files) that ``cells`` reads in place of the repository's."""
    chip = tmp_path / HERE.relative_to(cells.ROOT)
    for sub in ("configs", "traffic", "limits", "references", "programs",
                "layer_metrics"):
        shutil.copytree(HERE / sub, chip / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(cells, "ROOT", tmp_path)
    monkeypatch.setattr(cells, "HERE", chip)
    return tmp_path


def _add_ssm_cell(root: Path) -> None:
    """The Mamba-2 cell as new files (a program, a configuration, a plain
    reference, a traffic mix, limits) and new entries in BENCHMARK.json."""
    chip = cells.HERE
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (chip / "programs" / "mamba2_ssd.py").write_text(SSM_PROGRAM)
    (chip / "references" / "mamba2_ssd.py").write_text(SSM_REFERENCE)
    (chip / "configs" / "mamba2-small.json").write_text(json.dumps(SSM_CONFIG, sort_keys=True))
    (chip / "traffic" / "short_backlog.json").write_text(json.dumps(SSM_MIX, sort_keys=True))
    (chip / "limits" / f"{SSM_CELL}.json").write_text(json.dumps(SSM_LIMITS, sort_keys=True))
    bench["configs"].append({
        "name": "mamba2-small", "source": SSM_CONFIG["source"],
        "file": str((chip / "configs" / "mamba2-small.json").relative_to(root)),
        "reduced": sorted(SSM_CONFIG["changed_from_source"]),
        "why": "an attention-free Mamba-2 stack"})
    bench["workloads"].append({
        "name": SSM_CELL, "config": "mamba2-small",
        "traffic": "short_backlog", "chips": 1, "why": "SSD prefill and state decode"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, sort_keys=True))


def _run_cell(capsys, monkeypatch, cell, wrap=None):
    """``run.run_cell`` on the CPU: the result line, and what ``run.serve``
    returned to it."""
    import run

    served = []
    serve = run.serve
    monkeypatch.setattr(run, "serve", lambda *a, **k: served.append(serve(*a, **k)) or served[-1])
    assert run.run_cell(cell, 2**31 + 13, 1.5, trace=False, require_tpu=False, wrap=wrap) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1]), served[0]


def test_a_non_dense_configuration_is_new_files_only(harness_root, capsys, monkeypatch):
    from test_chipbench_run import _tiny

    before = _digest(harness_root)
    repo_before = _digest(HERE)
    old = json.loads((harness_root / "BENCHMARK.json").read_text())
    _add_ssm_cell(harness_root)

    cell = _tiny(SSM_CELL)
    assert cell.config["hidden_size"] == 64  # the program's own CPU cut
    cfg = model.program_config(cell.config)
    assert cfg.arch_type == "ssm" and cfg.ssm.d_state == 16
    rcfg = model.run_config(cell.config)
    assert rcfg.use_pallas is True is not model.run_config(_config("qwen2.5-3b")).use_pallas

    result, s = _run_cell(capsys, monkeypatch, cell)
    assert s.engine.ctx.cfg == cfg and s.engine.ctx.rcfg == rcfg
    # end-to-end metrics and a verdict from the cell's own plain reference
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    for name in ("max_gap", "logit_err"):
        assert result["checks"][name]["value"] <= result["checks"][name]["limit"]
    finished = [tr for tr in s.window.tracked if tr.request.output is not None]
    assert len(finished) >= int(cell.traffic["batch"])
    for tr in finished:
        out = np.asarray(tr.request.output)
        assert out.size == tr.planned.n_out
        assert ((out >= 0) & (out < cell.config["vocab_size"])).all()

    # the program's init rules built the SSM leaves (1/sqrt(fan_in) of a
    # (layers, heads) vector would give A above -1 and steps near 0.7)
    ssm = {k: np.asarray(v, np.float32) for k, v in s.engine.params["layers"]["ssm"].items()}
    a = np.exp(ssm["A_log"])
    assert (a >= 1.0).all() and (a <= 16.0 * 1.01).all() and a.std() > 1.0
    dt = np.log1p(np.exp(ssm["dt_bias"]))
    assert (dt >= 1e-3 * 0.95).all() and (dt <= 0.1 * 1.05).all()
    assert np.abs(ssm["D"] - 1.0).max() < 0.6 and ssm["D"].std() > 0.02

    # no file that the harness had changed, here or in the repository;
    # BENCHMARK.json only gained entries
    after = _digest(harness_root)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    now = json.loads((harness_root / "BENCHMARK.json").read_text())
    for key, entries in old.items():
        if key in ("configs", "workloads"):
            assert now[key][: len(entries)] == entries
        else:
            assert now[key] == entries
    assert set(after) - set(before) == {
        str((cells.HERE / p).relative_to(harness_root)) for p in SSM_ADDED}
    assert _digest(HERE) == repo_before


def test_a_non_dense_configuration_fails_an_altered_token(harness_root, capsys, monkeypatch):
    """The new cell's check is no formality: a token altered where the
    engine produces it comes out not correct."""
    import faults
    from test_chipbench_run import _tiny

    _add_ssm_cell(harness_root)
    result, _ = _run_cell(capsys, monkeypatch, _tiny(SSM_CELL), wrap=faults.AlteredToken)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
