"""decode_hbm_share: the bytes a decode step must read (every bf16 weight
matrix and the head once, the live keys and values of every row) over
the device time of the decode program, as a share of the chip's HBM
bandwidth. Device time: the trace's ``XLA Modules`` events named
``jit_serve_step`` (``repro.serving.engine.make_serve_step``); each is
matched to the context length the harness saw dispatched."""
LAYER = "engine"
MOVES = "tokens_per_s"
MATCHES = ("jit_serve_step",)


def read(run):
    import counts
    import xtrace

    if run.trace is None:
        return None
    events = run.programs(MATCHES[0])
    calls = run.dispatches(run.engine.decodes, len(events))
    if not events or len(calls) != len(events):
        return None
    moved = sum(counts.decode_bytes(run.dims, rows, ctx) for _, rows, ctx in calls)
    return 100.0 * moved / xtrace.seconds(events) / run.peaks["hbm_bytes_per_s"]
