"""flash_attn_roofline: the Pallas flash-attention kernel's least time
(the larger of its causal FLOPs over the bf16 peak and its bytes over
the HBM bandwidth, from shapes) over its device time. The kernel's calls
are the ``XLA Ops`` events named after its jitted entry point,
``flash_attention_bhsd`` (``repro.kernels.flash_attention``; the
``tpu_custom_call`` instruction takes that name), that lie inside a
``jit_prefill_step`` program execution; each such execution makes one
call per layer at the batch and prompt length that was dispatched.
The prompt lengths served here make the kernel compute-bound."""
LAYER = "kernels"
MOVES = "ttft_p95_ms"
MATCHES = ("flash_attention_bhsd",)
PROGRAM = "jit_prefill_step"


def read(run):
    import counts
    import xtrace

    if run.trace is None:
        return None
    programs = run.programs(PROGRAM)
    calls = run.dispatches(run.engine.prefills, len(programs))
    if not programs or len(calls) != len(programs):
        return None
    kernels = [e for e in xtrace.matching(run.trace.ops, MATCHES) if e.device == 0]
    least = spent = 0.0
    for prog, (_, rows, seq, _) in zip(programs, calls):
        inside = [k for k in kernels if prog.start <= k.start and k.end <= prog.end]
        if len(inside) != run.dims.layers:
            return None
        per_call = max(
            counts.flash_flops(run.dims, rows, seq) / run.peaks["bf16_flops_per_s"],
            counts.flash_bytes(run.dims, rows, seq) / run.peaks["hbm_bytes_per_s"],
        )
        least += per_call * len(inside)
        spent += xtrace.seconds(inside)
    return 100.0 * least / spent
