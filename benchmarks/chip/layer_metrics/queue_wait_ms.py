"""queue_wait_ms: median, over requests due in the window that reached a
slot, of ``Request.started`` (the runtime's prefill dispatch time) minus
the due time: the wait for admission. No trace names."""
LAYER = "runtime"
MOVES = "ttft_p95_ms"


def read(run):
    from reading import percentile

    waits = [
        tr.request.started - tr.due
        for tr in run.due_in_window()
        if tr.request.started > 0 and tr.request.started < run.window.t1
    ]
    p = percentile(waits, 50)
    return None if p is None else 1e3 * p
