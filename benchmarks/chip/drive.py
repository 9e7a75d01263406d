"""The measured window: open- or closed-loop traffic through the
program's ``ServingRuntime``, timed from the harness's side.

The harness submits each request when it falls due and times it from
that due moment. After every ``ServingRuntime.step()`` pass it stamps the
tokens that each request gained in the pass, so a token's time is exact
to one ring pass. Host spans (``bench.pass``, ``bench.submit``,
``bench.idle_wait``, ``bench.window``) go into the profiler's trace, so
that idle gaps on the device can be attributed to what the host did.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import traffic as traffic_lib


class StampingEngine:
    """A thin proxy of ``ServingEngine``: passes every call through and
    records what it dispatched (prefill shapes, decode context lengths),
    which the trace's program events are matched against.

    It also keeps the logits the timed path produced, for the
    correctness check: those of every prefill (the first token of each
    row) and of a seeded sample of decode steps (one in ``keep_every``).
    A kept decode's logits are read on the host after the pass that
    retired them, from the copy the runtime already made there; a
    prefill's (the runtime retires a slice of them) stay on the device
    until the window has closed.
    """

    def __init__(self, engine, keep_every: int = 0, seed: int = 0):
        self.engine = engine
        self.ctx = engine.ctx
        self.batch = engine.batch
        self.max_len = engine.max_len
        self.prefills: List[tuple] = []  # (t, rows, seq, rows holding a prompt)
        self.decodes: List[tuple] = []  # (t, rows, context)
        self.keep_every = keep_every
        self._rng = np.random.default_rng([seed, 2])
        self._meta: Dict[int, tuple] = {}  # id(cache) -> (group, prompt len, length)
        self.groups: List[Dict[bytes, int]] = []  # prompt bytes -> row, per group
        self.kept: List[list] = []  # [group, token index, logits, pass]
        self._unread: List[list] = []  # kept decodes not yet read on the host
        self._pass = 0

    def prefill(self, tokens, extras=None):
        cache, logits = self.engine.prefill(tokens, extras)
        rows = np.asarray(tokens)
        b, seq = rows.shape
        # the runtime pads a short group with rows of zeros, which no
        # drawn prompt is
        real = int(np.count_nonzero(rows.any(axis=1)))
        self.prefills.append((time.monotonic(), b, seq, real))
        gid = len(self.groups)
        self.groups.append({r.tobytes(): j for j, r in enumerate(rows) if r.any()})
        self._meta[id(cache)] = (gid, seq, seq)
        if self.keep_every:
            self.kept.append([gid, 0, logits, None])
        return cache, logits

    def decode(self, cache, tokens):
        gid, prompt, length = self._meta.pop(id(cache))
        new_cache, logits = self.engine.decode(cache, tokens)
        self.decodes.append((time.monotonic(), tokens.shape[0], length + 1))
        self._meta[id(new_cache)] = (gid, prompt, length + 1)
        if self.keep_every and self._rng.integers(self.keep_every) == 0:
            # these logits predict token length - prompt + 1 of each row
            self.kept.append([gid, length - prompt + 1, logits, self._pass])
            self._unread.append(self.kept[-1])
        return new_cache, logits

    def end_pass(self) -> None:
        """Read on the host the kept decode logits that this pass retired."""
        still = []
        for k in self._unread:
            if k[3] < self._pass:
                k[2] = np.asarray(k[2])
            else:
                still.append(k)
        self._unread = still
        self._pass += 1

    def end_window(self) -> None:
        for k in self.kept:
            k[2] = np.asarray(k[2])
        self._unread = []

    def kept_for(self, prompt: np.ndarray, n_tokens: int):
        """(token indices, (K, V) float32 logits) kept for the request with
        this prompt, among its ``n_tokens`` served tokens."""
        key = prompt.tobytes()
        found = [(g, row) for g, rows in enumerate(self.groups)
                 if (row := rows.get(key)) is not None]
        index, logits = [], []
        for g, row in found[-1:]:
            for gid, k, lg, _ in self.kept:
                if gid == g and k < n_tokens:
                    index.append(k)
                    logits.append(np.asarray(lg[row, -1], np.float32))
        if not index:
            return np.zeros(0, np.int64), np.zeros((0, 1), np.float32)
        order = np.argsort(index)
        return np.asarray(index)[order], np.stack(logits)[order]


@dataclasses.dataclass
class Tracked:
    planned: traffic_lib.Planned
    request: object  # repro.serving.Request
    due: float  # absolute monotonic due time (-inf for the backlog)
    stamps: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class WindowResult:
    t0: float  # window start (monotonic)
    t1: float  # window end
    tracked: List[Tracked]
    steps_in_window: int  # decode-group retires inside the window
    batch: int
    trace_dir: Optional[str] = None
    late_s: float = 0.0  # most that a submission ran behind its due time
    trace_stop: float = 0.0  # when tracing stopped (monotonic)
    queue: tuple = (0, 0)  # requests waiting for a slot at t0 and at t1

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1


def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def _request(p: traffic_lib.Planned):
    from repro.serving import Request

    return Request(p.rid, p.prompt, p.n_out)


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Serve one short request per prompt length through a runtime of the
    cell's shape, so that every program the window runs is compiled."""
    from repro.serving import ServingRuntime

    rt = ServingRuntime(engine, batch_size=int(mix["batch"]),
                        concurrency=int(mix["slots"]))
    for p in traffic_lib.warmup_requests(mix, vocab):
        rt.submit(_request(p))
    while rt.step():
        pass
    for r in rt.done:
        np.asarray(r.output)


def run_window(
    engine,
    mix: dict,
    seed: int,
    seconds: float,
    vocab: int,
    trace_dir: Optional[str] = None,
    trace_s: float = 0.0,
    on_window_start: Callable[[], None] = lambda: None,
) -> WindowResult:
    """Drive the lead-in and the window; with ``trace_dir`` the last
    ``trace_s`` seconds of the window are traced."""
    from repro.serving import ServingRuntime

    batch, slots = int(mix["batch"]), int(mix["slots"])
    rt = ServingRuntime(engine, batch_size=batch, concurrency=slots)
    backlog = mix["arrivals"] == "backlog"
    t_start = time.monotonic()
    t0 = t_start + float(mix["lead_s"])
    t1 = t0 + seconds
    if backlog:
        blocks = traffic_lib.backlog_blocks(mix, seed, vocab)
        keep = int(mix["backlog_groups"]) * batch * slots
        plan: List[traffic_lib.Planned] = []
    else:
        plan = traffic_lib.open_loop(mix, seed, vocab, seconds)
    tracked: List[Tracked] = []
    in_flight: List[Tracked] = []
    nxt = 0
    late = 0.0
    steps0 = None
    queue0 = 0
    tracing = trace_dir is not None
    trace_started = False
    window_span = None
    trace_stop = 0.0

    def submit(p: traffic_lib.Planned, due: float):
        tr = Tracked(p, _request(p), due)
        rt.submit(tr.request)
        tracked.append(tr)
        in_flight.append(tr)

    while True:
        now = time.monotonic()
        if steps0 is None and now >= t0:
            steps0 = rt.steps
            queue0 = len(rt.waiting)
            on_window_start()
        if tracing and not trace_started and now >= t1 - trace_s:
            # the window's last seconds: writing the trace out stalls the
            # host for seconds, which must fall after the window
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans, not every call
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            window_span = _span("bench.window")
            window_span.__enter__()
            trace_started = True
        if now >= t1:
            break
        with _span("bench.submit"):
            if backlog:
                while len(rt.waiting) < keep:
                    for p in next(blocks):
                        submit(p, -np.inf)
            else:
                while nxt < len(plan) and t0 + plan[nxt].due_s <= now:
                    due = t0 + plan[nxt].due_s
                    late = max(late, now - due)
                    submit(plan[nxt], due)
                    nxt += 1
        with _span("bench.pass"):
            progressed = rt.step()
        t = time.monotonic()
        engine.end_pass()
        still = []
        for tr in in_flight:
            n = len(tr.request.tokens)
            while len(tr.stamps) < n:
                tr.stamps.append(t)
            if len(tr.stamps) < tr.planned.n_out:
                still.append(tr)
        in_flight = still
        if not progressed:
            nxt_due = t0 + plan[nxt].due_s if nxt < len(plan) else t1
            with _span("bench.idle_wait"):
                time.sleep(max(0.0, min(nxt_due, t1) - time.monotonic()))
    if trace_started:
        trace_stop = _stop_trace(rt, window_span)
    steps = rt.steps - (steps0 or 0)
    queue = (queue0, len(rt.waiting))
    _settle(rt)
    engine.end_window()
    return WindowResult(t0, t1, tracked, steps, batch, trace_dir, late, trace_stop,
                        queue)


def _stop_trace(rt, window_span) -> float:
    """Close the traced window once every dispatched program has run."""
    _settle(rt)
    t = time.monotonic()
    window_span.__exit__(None, None, None)
    jax.profiler.stop_trace()
    return t


def _settle(rt) -> None:
    """Wait for every dispatched program, so none runs past the trace."""
    for ring in rt.tenants.values():
        for slot in ring.slots:
            if slot.logits is not None:
                slot.logits.block_until_ready()
