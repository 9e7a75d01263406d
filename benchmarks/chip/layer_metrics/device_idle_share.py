"""device_idle_share: the share of the traced window in which no
operation ran on the device (``XLA Ops`` events of each device plane,
union over time, averaged over the chips)."""
LAYER = "device"
MOVES = "tokens_per_s"


def read(run):
    import xtrace

    if run.trace is None or run.trace.n_devices == 0:
        return None
    span = run.trace_hi - run.trace_lo
    return 100.0 * (1.0 - xtrace.busy_s(run.trace, run.trace_lo, run.trace_hi) / span)
