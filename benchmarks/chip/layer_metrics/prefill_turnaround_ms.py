"""prefill_turnaround_ms: median, over requests whose prefill was
dispatched in the window and whose first token reached the host, of
``Request.first_token`` minus ``Request.started``: the prefill running
behind the other slot's decode, and the retire that returns its first
token. The rest of TTFT after ``queue_wait_ms``. No trace names; a
program without the stamp reads null."""
LAYER = "runtime"
MOVES = "ttft_p95_ms"
MATCHES = ("first_token", "started")


def read(run):
    from reading import percentile

    turns = []
    for tr in run.window.tracked:
        first = getattr(tr.request, MATCHES[0], 0.0)
        started = getattr(tr.request, MATCHES[1], 0.0)
        if first > 0 and run.window.in_window(started):
            turns.append(first - started)
    p = percentile(turns, 50)
    return None if p is None else 1e3 * p
