"""moe_experts_time_share: the share of the decode program's device time
spent in the held experts' grouped products: the ``XLA Ops`` events
named after the megablox kernel's jitted entry point, ``gmm`` (the
``tpu_custom_call`` instruction takes that name), inside each
``jit_serve_step`` program execution (three per layer), over those
executions' device time.

It is a share of the step and not of the HBM roofline: on a v5e XLA
slices each layer's expert weights out of their stack in a fusion before
the kernel, which then reads them from fast memory, so the kernel's own
time does not hold the weights' HBM reads (bytes over kernel time read
151-153 % of 819 GB/s there; PERF.md)."""
LAYER = "kernels"
MOVES = "tokens_per_s"
MATCHES = ("gmm",)
PROGRAM = "jit_serve_step"


def read(run):
    import xtrace

    if run.trace is None:
        return None
    layers = len(run.config.get("layer_types", ()))
    programs = run.programs(PROGRAM)
    if not programs or not layers:
        return None
    kernels = [e for e in xtrace.matching(run.trace.ops, MATCHES) if e.device == 0]
    spent = 0.0
    for prog in programs:
        inside = [k for k in kernels if prog.start <= k.start and k.end <= prog.end]
        if len(inside) != 3 * layers:
            return None
        spent += xtrace.seconds(inside)
    return 100.0 * spent / xtrace.seconds(programs)
