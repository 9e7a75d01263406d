"""Readings that a cell's correctness limit is set from.

    python3 benchmarks/chip/limits.py <cell> <seconds> <controls> <faults> <seed> ...

For each seed, in one process: serve one window of the cell's traffic
through the timed path, sample the finished requests as a run does, and
read the widest gap of the served tokens against the plain float32
reference (the program's reading), then the same prompts and tokens
through the reference with its matrices rounded to int8 and to fp8 (the
controls: the token that the lower precision ranks first at each
position, judged by the float32 reference) for the first ``controls``
seeds. The first ``faults`` seeds are served a second time with a token
altered where the engine produces it (``faults.AlteredToken``). Each
reading is the widest gap and the largest logit error. One JSON line per seed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import gc
import json
import sys

import faults
import run

CONTROLS = ("int8", "fp8")


def main(argv) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("limits.py: needs a TPU", file=sys.stderr)
        return 2
    cell = run.cells.resolve(argv[0])
    seconds = float(argv[1])
    n_controls, n_faults = int(argv[2]), int(argv[3])
    for k, seed in enumerate(int(a) for a in argv[4:]):
        s = run.serve(cell, seed, seconds)
        served, kept = run.served_tokens(cell, s.window, s.proxy, seed)
        params = s.engine.params
        row = {
            "cell": cell.name, "seed": seed,
            "sample_tokens": int(sum(t.size for _, t in served)),
            "failed": run.failed_requests(s.window),
            "program": run.readings(cell, params, served, kept),
        }
        for q in CONTROLS if k < n_controls else ():
            row[q] = run.readings(cell, params, served, kept, quant=q)
        del s, params
        gc.collect()
        if k < n_faults:
            s = run.serve(cell, seed, seconds, wrap=faults.AlteredToken)
            served, kept = run.served_tokens(cell, s.window, s.proxy, seed)
            row["altered_token"] = run.readings(cell, s.engine.params, served, kept)
            del s
            gc.collect()
        print(json.dumps(row, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
