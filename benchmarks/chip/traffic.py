"""Traffic generation from a mix file (``traffic/<mix>.json``) and a seed.

Every seed gets the same set of sizes and the same set of arrival gaps,
in another order: lengths are the quantiles of the mix's distribution and
Poisson gaps the quantiles of the exponential, shuffled by the seed. So a
seed changes which request comes when, never how much work a run holds,
and runs of different seeds spread no wider than runs of one seed.

Arrival processes (``arrivals`` in the mix file):

- ``poisson``: open loop at ``rate_rps``, due times from ``-lead_s`` (the
  lead-in before the measured window) to the window's end.
- ``backlog``: closed loop for offline batch generation. Requests come in
  blocks of ``batch`` whose output lengths are the same quantile set, so
  every decode group holds the same work; the driver keeps at least
  ``backlog_groups`` × batch × slots requests waiting.
- ``steady``, ``bursty_poisson``, ``diurnal``: the program's own trace
  shapes (``repro.serving.workload``), copied here so that the yardstick
  does not move with the program. They draw their gaps from the seed and
  are used by no cell yet.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float  # from the start of the measured window; negative = lead-in
    prompt: np.ndarray
    n_out: int


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` output lengths at the mid-quantiles of ``spec``'s distribution."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "log_uniform":
        lens = np.round(lo * (hi / lo) ** q)
    elif spec["dist"] == "uniform":
        lens = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(lens, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _prompt_lens(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lens = np.resize(np.asarray(mix["prompt_lens"], np.int64), n)
    return rng.permutation(lens)


def _materialize(due, prompt_lens, n_out, vocab, rng, rid0=0) -> List[Planned]:
    return [
        Planned(rid0 + i, float(t), rng.integers(0, vocab, int(p), dtype=np.int32),
                int(m))
        for i, (t, p, m) in enumerate(zip(due, prompt_lens, n_out))
    ]


def _shuffled_mix(mix, due, vocab, rng) -> List[Planned]:
    n = len(due)
    prompt_lens = _prompt_lens(mix, n, rng)
    n_out = rng.permutation(quantile_lengths(mix["output_lens"], n))
    return _materialize(due, prompt_lens, n_out, vocab, rng)


def open_loop(mix: dict, seed: int, vocab: int, seconds: float) -> List[Planned]:
    """Requests due from ``-lead_s`` to ``seconds``, in due order."""
    rng = np.random.default_rng(seed)
    lead = float(mix["lead_s"])
    horizon = lead + seconds
    kind = mix["arrivals"]
    if kind == "poisson":
        n = int(round(mix["rate_rps"] * horizon))
        due = np.cumsum(rng.permutation(exponential_gaps(mix["rate_rps"], n)))
    elif kind == "steady":
        due = np.arange(0.0, horizon, 1.0 / mix["rate_rps"])
    elif kind == "bursty_poisson":
        due = _bursty_times(mix, horizon, rng)
    elif kind == "diurnal":
        due = _diurnal_times(mix, horizon, rng)
    else:
        raise ValueError(f"{kind!r} is not an open-loop arrival process")
    due = np.asarray(due, np.float64) - lead
    return [p for p in _shuffled_mix(mix, due, vocab, rng) if p.due_s < seconds]


def backlog_blocks(mix: dict, seed: int, vocab: int) -> Iterator[List[Planned]]:
    """Endless blocks of ``batch`` requests for the closed-loop backlog."""
    rng = np.random.default_rng(seed)
    b = int(mix["batch"])
    block_lens = quantile_lengths(mix["output_lens"], b)
    for k in itertools.count():
        prompt_lens = _prompt_lens(mix, b, rng)
        yield _materialize(
            [-math.inf] * b, prompt_lens, rng.permutation(block_lens), vocab, rng,
            rid0=k * b,
        )


def warmup_requests(mix: dict, vocab: int) -> List[Planned]:
    """One short request per prompt length: compiles each prefill shape,
    the decode step and the runtime's slicing before the window."""
    rng = np.random.default_rng(0)
    lens = sorted(set(int(p) for p in mix["prompt_lens"]))
    return _materialize([-math.inf] * len(lens), lens, [2] * len(lens), vocab, rng,
                        rid0=-len(lens))


# -- copies of repro.serving.workload's arrival shapes -----------------------


def _bursty_times(mix: dict, horizon: float, rng: np.random.Generator) -> list:
    """Poisson arrivals alternating between a calm and a ``burst_factor``×
    rate every ``phase_s`` seconds; the duty cycle averages to ``rate_rps``."""
    rate, factor = float(mix["rate_rps"]), float(mix.get("burst_factor", 4.0))
    phase_s = float(mix.get("phase_s", 0.5))
    calm = 2.0 * rate / (1.0 + factor)
    times = []
    t = float(rng.exponential(1.0 / rate))
    while t < horizon:
        times.append(t)
        lam = calm * factor if int(t / phase_s) % 2 == 1 else calm
        t += float(rng.exponential(1.0 / lam))
    return times


def _diurnal_times(mix: dict, horizon: float, rng: np.random.Generator) -> list:
    """Poisson arrivals whose rate swings ±``depth`` around ``rate_rps`` with
    period ``period_s``, sampled by thinning."""
    rate = float(mix["rate_rps"])
    period, depth = float(mix.get("period_s", 4.0)), float(mix.get("depth", 0.8))
    lam_max = rate * (1.0 + depth)
    times = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / lam_max))
        if t >= horizon:
            return times
        lam_t = rate * (1.0 + depth * np.sin(2.0 * np.pi * t / period))
        if rng.uniform() * lam_max <= lam_t:
            times.append(t)
