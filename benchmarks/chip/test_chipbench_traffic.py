"""Traffic generation reproduces from the seed, and every seed holds the
same work."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import traffic  # noqa: E402

BIG_SEED = 2**31 + 12345  # wider than 32 signed bits


def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _key(plan):
    return [(p.rid, round(p.due_s, 9), p.n_out, p.prompt.tobytes()) for p in plan]


def test_open_loop_reproduces_from_seed():
    mix = _mix("chat")
    a = traffic.open_loop(mix, BIG_SEED, 151936, 40.0)
    b = traffic.open_loop(mix, BIG_SEED, 151936, 40.0)
    c = traffic.open_loop(mix, BIG_SEED + 1, 151936, 40.0)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_open_loop_same_work_for_every_seed():
    mix = _mix("long_prompt")
    full = traffic.open_loop(mix, 1, 151936, 40.0)
    n = len(full)
    for seed in (2, BIG_SEED):
        other = traffic.open_loop(mix, seed, 151936, 40.0)
        assert len(other) == n
        assert sorted(p.n_out for p in other) == sorted(p.n_out for p in full)
        assert Counter(p.prompt.size for p in other) == Counter(
            p.prompt.size for p in full)
        gaps = np.diff([-mix["lead_s"]] + [p.due_s for p in other])
        want = np.diff([-mix["lead_s"]] + [p.due_s for p in full])
        np.testing.assert_allclose(np.sort(gaps), np.sort(want), rtol=1e-9)
    for seed in (1, 2, BIG_SEED):
        due = [p.due_s for p in traffic.open_loop(mix, seed, 151936, 40.0)]
        assert due == sorted(due)
        assert due[0] >= -mix["lead_s"] and 38.0 < due[-1] < 40.0


def test_poisson_rate_and_lengths():
    mix = _mix("chat")
    plan = traffic.open_loop(mix, 7, 151936, 40.0)
    horizon = mix["lead_s"] + 40.0
    assert abs(len(plan) / horizon - mix["rate_rps"]) < 0.05 * mix["rate_rps"]
    assert {p.prompt.size for p in plan} == set(mix["prompt_lens"])
    outs = np.array([p.n_out for p in plan])
    lo, hi = mix["output_lens"]["lo"], mix["output_lens"]["hi"]
    assert outs.min() >= lo and outs.max() <= hi
    assert all(p.prompt.max() < 151936 for p in plan)


def test_backlog_blocks_hold_the_same_lengths():
    mix = _mix("offline")
    blocks = traffic.backlog_blocks(mix, BIG_SEED, 49152)
    first = [next(blocks) for _ in range(3)]
    again = traffic.backlog_blocks(mix, BIG_SEED, 49152)
    assert _key(sum(first, [])) == _key(sum([next(again) for _ in range(3)], []))
    want = sorted(traffic.quantile_lengths(mix["output_lens"], mix["batch"]))
    for block in first:
        assert len(block) == mix["batch"]
        assert sorted(p.n_out for p in block) == want
        assert {p.prompt.size for p in block} == {512}
    assert [p.n_out for p in first[0]] != [p.n_out for p in first[1]]


def test_quantile_lengths():
    spec = {"dist": "log_uniform", "lo": 64, "hi": 512}
    q = traffic.quantile_lengths(spec, 16)
    assert q[0] == 68 and q[-1] == 480  # 64·8^(0.5/16), 64·8^(15.5/16)
    u = traffic.quantile_lengths({"dist": "uniform", "lo": 16, "hi": 64}, 49)
    assert list(u) == list(range(16, 65))
