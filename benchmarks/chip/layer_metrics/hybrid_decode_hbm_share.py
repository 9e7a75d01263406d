"""hybrid_decode_hbm_share: the bytes a decode step of the Mamba-2 /
attention / expert stack must move (every bf16 weight of the stage once,
all held experts and the tied head included; every row's SSM and conv
state read and written; the attention layers' live keys and values)
over the device time of the decode program, as a share of the chip's HBM
bandwidth. Device time: the trace's ``XLA Modules`` events named
``jit_serve_step`` (``repro.serving.engine.make_engine_decode``), each
matched to the rows and context length the harness saw dispatched. The
counts are the configuration program's (``programs/<name>.py``)."""
LAYER = "engine"
MOVES = "tokens_per_s"
MATCHES = ("jit_serve_step",)


def read(run):
    import cells
    import xtrace

    if run.trace is None:
        return None
    program = cells.program_module(run.config)
    if not hasattr(program, "decode_bytes"):
        return None
    events = run.programs(MATCHES[0])
    calls = run.dispatches(run.engine.decodes, len(events))
    if not events or len(calls) != len(events):
        return None
    moved = sum(program.decode_bytes(run.config, rows, ctx) for _, rows, ctx in calls)
    return 100.0 * moved / xtrace.seconds(events) / run.peaks["hbm_bytes_per_s"]
