"""mfu: model FLOPs of every token served in the window (a first token
costs its prompt's prefill, each later one a decode row at its context)
over the window's length, as a share of the chip's bf16 peak. Host
clock and token stamps; no trace names."""
LAYER = "serving loop"
MOVES = "tokens_per_s"


def read(run):
    import counts

    if run.peaks is None:
        return None
    flops = sum(
        counts.token_flops(run.dims, tr.planned.prompt.size, i)
        for tr, i, _ in run.window_tokens()
    )
    return 100.0 * flops / run.window.seconds / run.peaks["bf16_flops_per_s"]
