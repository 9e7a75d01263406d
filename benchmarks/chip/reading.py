"""What one run leaves for the metrics to read, and the end-to-end
metrics themselves (host clock)."""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import counts
import xtrace
from drive import StampingEngine, WindowResult


@dataclasses.dataclass
class RunRecord:
    config: dict
    mix: dict
    window: WindowResult
    engine: StampingEngine
    peaks: Optional[dict] = None
    trace: Optional[xtrace.Trace] = None
    trace_lo: float = 0.0  # the traced window on the trace's clock
    trace_hi: float = 0.0
    trace_stop: float = 0.0  # when tracing stopped, on the host's clock

    @functools.cached_property
    def dims(self) -> counts.Dims:
        """The dense decoder's sizes that the FLOP and byte counts take,
        worked out when a reader first asks: a configuration of another
        family need not have them, and a run that reads no count never
        asks."""
        return counts.Dims.of(self.config)

    def window_tokens(self) -> List[Tuple[object, int, float]]:
        """(tracked request, token index, stamp) of every token in the window."""
        w = self.window
        return [
            (tr, i, t)
            for tr in w.tracked
            for i, t in enumerate(tr.stamps)
            if w.in_window(t)
        ]

    def due_in_window(self):
        return [tr for tr in self.window.tracked if self.window.in_window(tr.due)]

    def programs(self, name: str) -> List[xtrace.Event]:
        """Executions of program ``name`` wholly inside the traced window."""
        return [
            e
            for e in xtrace.matching(self.trace.modules, [name])
            if e.device == 0 and e.start >= self.trace_lo and e.end <= self.trace_hi
        ]

    def dispatches(self, calls: Sequence[tuple], n: int) -> Sequence[tuple]:
        """The last ``n`` calls dispatched before tracing stopped: the ones
        whose executions the trace holds (every dispatch ran before the
        trace stopped)."""
        before = [c for c in calls if c[0] <= self.trace_stop]
        return before[len(before) - n :] if n else []


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def tokens_per_s(run: RunRecord) -> float:
    return len(run.window_tokens()) / run.window.seconds


def ttft_p95_ms(run: RunRecord) -> Optional[float]:
    """First-token time minus due time, over every request due in the
    window; one with no first token by the window's end enters with its
    wait so far."""
    w = run.window
    waits = [
        (min(tr.stamps[0], w.t1) if tr.stamps else w.t1) - tr.due
        for tr in run.due_in_window()
    ]
    p = percentile(waits, 95)
    return None if p is None else 1e3 * p


def itl_p95_ms(run: RunRecord) -> Optional[float]:
    """Every gap between consecutive output tokens inside the window."""
    w = run.window
    gaps = [
        b - a
        for tr in w.tracked
        for a, b in zip(tr.stamps, tr.stamps[1:])
        if w.t0 <= a and b < w.t1
    ]
    p = percentile(gaps, 95)
    return None if p is None else 1e3 * p


END_TO_END = {
    "tokens_per_s": tokens_per_s,
    "ttft_p95_ms": ttft_p95_ms,
    "itl_p95_ms": itl_p95_ms,
}

