"""The trace reduction on a small synthetic trace, and ``load`` on a
trace recorded here."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import xtrace  # noqa: E402
from xtrace import Event, Trace  # noqa: E402


def _trace():
    # device 0: a loop [0, 6] holding ops [0,1], [1,3], [4,6]; a kernel [7,8]
    # device 1: one op [2, 5]
    ops = [
        Event("%while.3", 0.0, 6.0, 0),
        Event("%fusion.1", 0.0, 1.0, 0),
        Event("%fusion.2", 1.0, 3.0, 0),
        Event("%fusion.1", 4.0, 6.0, 0),
        Event("%flash_attention_bhsd.6", 7.0, 8.0, 0),
        Event("%fusion.9", 2.0, 5.0, 1),
    ]
    modules = [Event("jit_serve_step(7)", 0.0, 6.0, 0),
               Event("jit_prefill_step(3)", 6.5, 8.5, 0)]
    host = [
        Event("bench.window", 0.0, 10.0),
        Event("bench.pass", 0.0, 6.5),
        Event("bench.pass", 6.5, 9.0),
        Event("bench.idle_wait", 9.0, 10.0),
    ]
    return Trace(ops, modules, host, n_devices=2)


def test_merge_and_clip():
    assert xtrace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    clipped = xtrace.clip([Event("a", -1.0, 1.0), Event("b", 5.0, 6.0)], 0.0, 2.0)
    assert [(e.name, e.start, e.end) for e in clipped] == [("a", 0.0, 1.0)]


def test_busy_union_and_idle_share():
    tr = _trace()
    # device 0 busy [0, 6] and [7, 8] = 7 s; device 1 busy 3 s; mean 5 s
    assert xtrace.busy_s(tr, 0.0, 10.0) == pytest.approx(5.0)
    # a window cuts the intervals: [5, 10] -> dev0 1 + 1, dev1 0 -> mean 1
    assert xtrace.busy_s(tr, 5.0, 10.0) == pytest.approx(1.0)
    assert xtrace.idle_gaps(tr, 0.0, 10.0) == [(6.0, 7.0), (8.0, 10.0)]


def test_gaps_by_host_span():
    # gap [6, 7]: half in each pass; gap [8, 10]: 1 s in the second pass,
    # 1 s in the idle wait
    assert xtrace.gaps_by_host(_trace(), 0.0, 10.0) == [
        ["bench.pass", pytest.approx(2.0)],
        ["bench.idle_wait", pytest.approx(1.0)],
    ]
    # the frame span is never blamed
    tr = _trace()
    tr.host = [Event("bench.window", 0.0, 10.0)]
    assert xtrace.gaps_by_host(tr, 0.0, 10.0) == [["no host span", pytest.approx(3.0)]]


def test_time_by_program_and_kernel_name():
    tr = _trace()
    serve = xtrace.matching(tr.modules, ["jit_serve_step"])
    assert xtrace.seconds(serve) == pytest.approx(6.0)
    flash = xtrace.matching(tr.ops, ["flash_attention_bhsd"])
    assert [e.name for e in flash] == ["%flash_attention_bhsd.6"]
    # innermost operations only: the loop does not count its body twice
    assert xtrace.top_ops(tr, 0.0, 10.0) == [
        ["%fusion.1", pytest.approx(3.0)],
        ["%fusion.2", pytest.approx(2.0)],
        ["%flash_attention_bhsd.6", pytest.approx(1.0)],
    ]
    assert tr.host_span("bench.pass") == (0.0, 9.0)


def test_op_name_keeps_the_instruction_name():
    text = "%fusion.148 = bf16[16,11008]{1,0} fusion(bf16[36,2048,11008] %get-tuple-element.517)"
    assert xtrace.op_name(text) == "%fusion.148"
    assert xtrace.op_name("jit_serve_step(12)") == "jit_serve_step(12)"


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.pass"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xtrace.load(xtrace.find_xplane(str(tmp_path)))
    names = sorted(e.name for e in tr.host)
    assert names == ["bench.pass", "bench.pass", "bench.window"]
    lo, hi = tr.host_span("bench.window")
    passes = [e for e in tr.host if e.name == "bench.pass"]
    assert all(lo <= e.start and e.end <= hi for e in passes)
