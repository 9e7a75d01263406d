"""row_occupancy: output tokens in the window over (decode-group retires
in the window × batch). The runtime decodes a group until its longest
row ends and refills by whole groups, so finished rows ride along; this
is the share of rows that still produced a token.

Reads ``ServingRuntime.steps`` (one per retire of a slot) and the
harness's token stamps; no trace names.
"""
LAYER = "runtime"
MOVES = "tokens_per_s"


def read(run):
    steps = run.window.steps_in_window
    if not steps:
        return None
    return 100.0 * len(run.window_tokens()) / (steps * run.window.batch)
