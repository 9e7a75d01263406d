"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 benchmarks/chip/sweep.py <cell> <seconds> <rate> [<rate> ...]

One process, one set of weights (seed 0), one window per offered rate
(requests/s), the cell's own mix otherwise. Per rate it prints what was
completed in the window, the queue of requests waiting for a slot at the
window's start and end, the requests due in the window that never
reached a slot, and the TTFT median and 95th percentile. Below the knee
the queue stays flat and the completions keep up with the offer; above
it the queue grows through the window.
"""
from __future__ import annotations

import json
import sys

import numpy as np

import run


def main(argv) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    import drive
    import model
    import reading
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    cell = run.cells.resolve(argv[0])
    seconds = float(argv[1])
    vocab = int(cell.config["vocab_size"])
    engine = drive.StampingEngine(model.build_engine(cell.config, cell.traffic, 0))
    drive.warm_up(engine, cell.traffic, vocab)
    for rate in (float(a) for a in argv[2:]):
        mix = dict(cell.traffic, rate_rps=rate)
        w = drive.run_window(engine, mix, 0, seconds, vocab)
        rec = reading.RunRecord(cell.config, mix, w, engine)
        due = rec.due_in_window()
        done = [tr for tr in w.tracked
                if tr.stamps and len(tr.stamps) == tr.planned.n_out
                and w.in_window(tr.stamps[-1])]
        waits = [(tr.stamps[0] if tr.stamps else w.t1) - tr.due for tr in due]
        print(json.dumps({
            "cell": cell.name, "rate_rps": rate, "seconds": seconds,
            "due": len(due), "completed_rps": len(done) / seconds,
            "tokens_per_s": reading.tokens_per_s(rec),
            "queue_start": w.queue[0], "queue_end": w.queue[1],
            "never_started": sum(1 for tr in due if not tr.request.started),
            "ttft_p50_ms": 1e3 * float(np.percentile(waits, 50)),
            "ttft_p95_ms": reading.ttft_p95_ms(rec),
            "itl_p95_ms": reading.itl_p95_ms(rec),
        }, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
