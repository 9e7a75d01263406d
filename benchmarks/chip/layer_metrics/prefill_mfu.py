"""prefill_mfu: model FLOPs of each prefill (every layer's projections,
causal attention over the prompt, the head at the last position; real
prompt rows only) over the device time of the prefill program, as a
share of the chip's bf16 peak. Device time: ``XLA Modules`` events named
``jit_prefill_step`` (``repro.serving.engine.make_prefill_step``)."""
LAYER = "engine"
MOVES = "ttft_p95_ms"
MATCHES = ("jit_prefill_step",)


def read(run):
    import counts
    import xtrace

    if run.trace is None:
        return None
    events = run.programs(MATCHES[0])
    calls = run.dispatches(run.engine.prefills, len(events))
    if not events or len(calls) != len(events):
        return None
    flops = sum(counts.prefill_flops(run.dims, real, seq) for _, _, seq, real in calls)
    return 100.0 * flops / xtrace.seconds(events) / run.peaks["bf16_flops_per_s"]
