"""The program's spans and scopes in a trace: ``program_trace.load`` and
its reductions on synthetic traces and on a trace recorded here, and the
readings that use them."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import program_trace as pt  # noqa: E402
import reading  # noqa: E402
import xtrace  # noqa: E402
from drive import Tracked, WindowResult  # noqa: E402

OPS = [
    # device 0: a loop [0, 6] holding ops [0, 1], [1, 3], [4, 6]; a kernel [7, 8]
    ("%while.3", 0.0, 6.0, 0, ""),
    ("%fusion.1", 0.0, 1.0, 0, "jit(serve_step)/embed/gather"),
    ("%fusion.2", 1.0, 3.0, 0, "jit(serve_step)/while/body/closed_call/kv_write/dus"),
    ("%fusion.1", 4.0, 6.0, 0, "jit(serve_step)/while/body/closed_call/mlp/dot"),
    ("%flash_attention_bhsd.6", 7.0, 8.0, 0, "jit(prefill_step)/while/body/attn/pallas"),
    # device 1
    ("%fusion.9", 2.0, 5.0, 1, "jit(serve_step)/head/dot"),
]
MODULES = [("jit_serve_step(7)", 0.0, 6.0), ("jit_prefill_step(3)", 6.5, 8.5)]
HOST = [("bench.window", 0.0, 10.0), ("bench.pass", 0.0, 6.5), ("bench.pass", 6.5, 9.0),
        ("bench.idle_wait", 9.0, 10.0)]
# inside the passes; gaps on device 0 are [6, 7] and [8, 10]
PROGRAM = [("serve.retire.wait", 5.8, 6.4, 1), ("serve.retire.copy", 6.4, 6.45, 1),
           ("serve.retire.sample", 6.45, 6.5, 1), ("serve.retire.book", 6.5, 6.6, 1),
           ("serve.decode.dispatch", 6.6, 6.7, 1), ("serve.admit", 8.0, 8.5, None),
           ("serve.retire.copy", 8.5, 8.6, 2), ("serve.retire.sample", 8.6, 8.64, 2)]


def _plain() -> xtrace.Trace:
    return xtrace.Trace(
        [xtrace.Event(n, s, t, d) for n, s, t, d, _ in OPS],
        [xtrace.Event(n, s, t, 0) for n, s, t in MODULES],
        [xtrace.Event(n, s, t) for n, s, t in HOST], n_devices=2)


def _with_program() -> pt.Trace:
    return pt.Trace(
        [pt.Event(n, s, t, d, scope=p) for n, s, t, d, p in OPS],
        [xtrace.Event(n, s, t, 0) for n, s, t in MODULES],
        [xtrace.Event(n, s, t) for n, s, t in HOST], 2,
        program=[pt.Event(n, s, t, args=(() if g is None else (("group", g),)))
                 for n, s, t, g in PROGRAM])


def test_program_spans_leave_the_harness_reductions_as_they_were():
    plain, full = _plain(), _with_program()
    for lo, hi in [(0.0, 10.0), (5.0, 9.5)]:
        assert xtrace.gaps_by_host(full, lo, hi) == xtrace.gaps_by_host(plain, lo, hi)
        assert xtrace.top_ops(full, lo, hi) == xtrace.top_ops(plain, lo, hi)
        assert xtrace.busy_s(full, lo, hi) == xtrace.busy_s(plain, lo, hi)
        assert xtrace.idle_gaps(full, lo, hi) == xtrace.idle_gaps(plain, lo, hi)


def test_idle_gaps_go_to_the_innermost_span():
    # gap [6, 7]: wait 0.4, copy 0.05, sample 0.05, book 0.1, dispatch 0.1,
    # then bench.pass 0.3 that no program span covers; gap [8, 10]: admit
    # 0.5, copy 0.1, sample 0.04, bench.pass 0.36, bench.idle_wait 1
    got = dict(pt.idle_gaps_program(_with_program(), 0.0, 10.0))
    assert got == pytest.approx({
        "serve.retire.wait": 0.4, "serve.retire.copy": 0.15,
        "serve.retire.sample": 0.09, "serve.retire.book": 0.1,
        "serve.decode.dispatch": 0.1, "serve.admit": 0.5,
        "bench.pass": 0.66, "bench.idle_wait": 1.0,
    })
    assert sum(got.values()) == pytest.approx(3.0)  # every idle second, once
    # a gap no span covers at all
    tr = _with_program()
    tr.host, tr.program = [], []
    assert pt.idle_gaps_program(tr, 0.0, 10.0) == [["no host span", pytest.approx(3.0)]]


def test_device_scopes_group_innermost_ops_by_program_and_scope():
    # device 0 only; the loop's own span does not count, the op outside
    # every program execution neither
    assert dict(pt.device_scopes(_with_program(), 0.0, 10.0)) == pytest.approx({
        "jit_serve_step/embed": 1.0, "jit_serve_step/kv_write": 2.0,
        "jit_serve_step/mlp": 2.0, "jit_prefill_step/attn": 1.0,
    })
    assert pt.scope_of("jit(serve_step)/while/body/dynamic_update_slice") == "other"
    assert pt.scope_of("jit(f)/kv_write/attn/x") == "kv_write"  # the first
    assert pt.program_name("jit_serve_step(12)") == "jit_serve_step"


def test_span_names_drop_the_argument_suffix():
    assert pt.span_name("serve.retire.copy#group=3#") == "serve.retire.copy"
    assert pt.span_name("serve.admit") == "serve.admit"


def test_load_keeps_program_spans_apart_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for g in (1, 2):
            with jax.profiler.TraceAnnotation("bench.pass"):
                with jax.profiler.TraceAnnotation("serve.retire.copy", group=g):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("serve.admit"):
                    pass
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(str(tmp_path))
    tr, plain = pt.load(path), xtrace.load(path)
    assert sorted((e.name, e.args) for e in tr.program) == [
        ("serve.admit", ()), ("serve.admit", ()),
        ("serve.retire.copy", (("group", 1),)), ("serve.retire.copy", (("group", 2),)),
    ]
    lo, hi = tr.host_span("bench.window")
    assert all(lo <= e.start and e.end <= hi for e in tr.program)
    # what xtrace.load gives, unchanged
    assert tr.host == plain.host and tr.modules == plain.modules
    assert [(e.name, e.start, e.end, e.device) for e in tr.ops] == [
        (e.name, e.start, e.end, e.device) for e in plain.ops]
    assert tr.n_devices == plain.n_devices


class _Request:
    def __init__(self, started, first_token=None):
        self.started = started
        if first_token is not None:
            self.first_token = first_token


def _run(trace, tracked=()) -> reading.RunRecord:
    window = WindowResult(0.0, 10.0, [Tracked(None, r, 0.0) for r in tracked], 0, 16)
    return reading.RunRecord({}, {}, window, None, None, trace, 0.0, 10.0)


def test_readings_of_the_program_spans():
    run = _run(_with_program())
    assert pt.logits_copy_ms(run) == pytest.approx(75.0)  # median of 50 and 100 ms
    # (sample 50 + 40, book 100, dispatch 100 ms) over 2 retires
    assert pt.retire_host_ms(run) == pytest.approx(145.0)
    # kv_write 2 s of jit_serve_step's 6 s
    assert pt.kv_write_share(run) == pytest.approx(100.0 * 2.0 / 6.0)


def test_readings_are_null_without_their_names():
    # a trace from xtrace.load (no program spans, no scopes): a program
    # without the spans, or a renamed span or scope
    run = _run(_plain())
    assert pt.logits_copy_ms(run) is None
    assert pt.retire_host_ms(run) is None
    assert pt.kv_write_share(run) is None
    renamed = _with_program()
    renamed.program = [pt.Event(e.name.replace("retire", "ret"), e.start, e.end)
                       for e in renamed.program]
    assert pt.logits_copy_ms(_run(renamed)) is None


def test_prefill_turnaround_reads_the_first_token_stamp():
    reader = cells.layer_reader("prefill_turnaround_ms")
    # started in the window: 0.2 s and 0.4 s; before it, and no first
    # token yet, do not count
    tracked = [_Request(1.0, 1.2), _Request(2.0, 2.4), _Request(-1.0, 0.5),
               _Request(9.9, 0.0)]
    assert reader.read(_run(None, tracked)) == pytest.approx(300.0)
    # a program without the stamp
    assert reader.read(_run(None, [_Request(1.0), _Request(2.0)])) is None


def test_measure_reads_the_program_spans_of_a_served_window():
    """The command's path end to end on the CPU at a tiny size: the CPU
    trace holds the runtime's spans but no device plane."""
    from test_chipbench_run import _tiny

    out = pt.measure(_tiny("qwen2.5-3b.chat"), seed=2**31 + 11, seconds=1.5,
                     trace=True, trace_s=1.0, peaks=None)
    assert out["tokens_per_s"] > 0 and out["decodes_per_s"] > 0
    assert out["passes_per_s_traced"] > 0
    readings = out["readings"]
    assert readings["logits_copy_ms"] > 0 and readings["retire_host_ms"] > 0
    assert readings["kv_write_share"] is None  # no device operations here
    assert out["metrics"]["prefill_turnaround_ms"] > 0
    programs = dict(out["breakdown"]["idle_gaps_program"])
    assert "serve.retire.wait" in programs


def test_program_paths_name_every_scope_of_the_compiled_programs():
    import numpy as np

    import model
    from test_chipbench_run import _tiny

    cell = _tiny("qwen2.5-3b.offline")
    engine = model.build_engine(cell.config, cell.traffic, 3)
    calls = pt.ProgramCalls(engine)
    cache, _ = calls.prefill(np.ones((engine.batch, 16), np.int32))
    calls.decode(cache, np.ones((engine.batch, 1), np.int32))
    paths = pt.program_paths(engine, calls)
    for program in ("jit_serve_step", "jit_prefill_step"):
        assert {pt.scope_of(p) for p in paths[program].values()} >= set(pt.SCOPES)


DEVICE_PLANE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000000 }
          events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 2000000000 }
          events { metadata_id: 2 offset_ps: 7000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_serve_step(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[2] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "%dynamic_update_slice.3 = bf16[2] dus()" } }
}
"""


def test_load_gives_each_device_op_the_path_of_its_instruction(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(DEVICE_PLANE))
    paths = {"jit_serve_step": {"%fusion.1": "jit(serve_step)/mlp/dot",
                                "%dynamic_update_slice.3": "jit(serve_step)/kv_write/dus"}}
    tr = pt.load(str(path), paths)
    # the third op ran outside every program execution: no path
    assert [(e.name, e.scope) for e in tr.ops] == [
        ("%fusion.1", "jit(serve_step)/mlp/dot"),
        ("%dynamic_update_slice.3", "jit(serve_step)/kv_write/dus"),
        ("%fusion.1", "")]
    assert dict(pt.device_scopes(tr, 0.0, 1.0)) == pytest.approx(
        {"jit_serve_step/mlp": 1e-3, "jit_serve_step/kv_write": 2e-3})
    plain = xtrace.load(str(path))
    assert tr.modules == plain.modules and tr.n_devices == plain.n_devices == 1
    assert [(e.name, e.start, e.end) for e in tr.ops] == [
        (e.name, e.start, e.end) for e in plain.ops]
    assert all(e.scope == "" for e in pt.load(str(path)).ops)


def test_instruction_paths_read_the_op_name_of_each_instruction():
    text = """
  %fusion.148 = bf16[16,11008]{1,0} fusion(%p.1), kind=kOutput, calls=%fc.6, metadata={op_name="jit(serve_step)/while/body/closed_call/mlp/dot_general" stack_frame_id=101}
  ROOT %copy.4 = bf16[2]{0} copy(%x), metadata={op_name="jit(serve_step)/head/dot_general"}
  %param.1 = bf16[2]{0} parameter(0)
"""
    assert pt.instruction_paths(text) == {
        "%fusion.148": "jit(serve_step)/while/body/closed_call/mlp/dot_general",
        "%copy.4": "jit(serve_step)/head/dot_general",
    }
