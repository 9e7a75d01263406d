"""The program's own spans and named scopes in a profiler trace, and the
readings they give.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--trace 0|1]

``xtrace.load`` keeps the harness's spans alone. ``load`` here reads the
same ``.xplane.pb`` with two additions: the serving runtime's host spans
(names that start with ``serve.``, ``repro.serving.spans``) in
``Trace.program``, each with its arguments, and each device operation's
named-scope path (``models/transformer.py``: ``embed``, ``attn``,
``kv_write``, ``mlp``, ``head``) in ``Event.scope``. ``ops``,
``modules`` and ``host`` hold what ``xtrace.load`` gives, so every
reduction of ``xtrace`` reads the same from either.

A v5e trace gives a device operation its HLO instruction and its times,
but not the op_name path its named scope left in the program's metadata.
So the paths come from the programs themselves: ``program_paths``
compiles the serving programs again for the shapes the window ran and
reads each instruction's ``op_name`` from their HLO text. JAX's compile
cache leaves metadata out of its key by default, so an executable it
hands back may carry the metadata of an older checkout; these compiles,
and the window's in the command, key on the metadata too.

The command serves one window of a cell as ``run.py`` does, with the
profiler on over its last 8 s as ``run.py --trace 1`` has it, and prints
one JSON line: tokens and decode dispatches per second, the cell's
per-layer metrics, the readings below and the trace's breakdowns. It
runs no correctness check. With ``--trace 0`` it prints the rates alone:
the same cell and seed with and without the profiler give the cost of
tracing.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import re
import statistics
from typing import Dict, List, Optional, Tuple

import reading
import xtrace

PROGRAM_PREFIX = "serve."
SCOPES = ("embed", "attn", "kv_write", "mlp", "head")
OTHER = "other"

RETIRE_COPY = "serve.retire.copy"
RETIRE_SAMPLE = "serve.retire.sample"
RETIRE_HOST = (RETIRE_SAMPLE, "serve.retire.book", "serve.decode.dispatch")
DECODE_PROGRAM = "jit_serve_step"
PREFILL_PROGRAM = "jit_prefill_step"
KV_WRITE = "kv_write"


@dataclasses.dataclass(frozen=True)
class Event(xtrace.Event):
    scope: str = ""  # a device operation's op_name path
    args: Tuple[Tuple[str, object], ...] = ()  # a program span's arguments


@dataclasses.dataclass
class Trace(xtrace.Trace):
    program: List[Event] = dataclasses.field(default_factory=list)


def load(path: str, paths: Optional[Dict[str, Dict[str, str]]] = None) -> Trace:
    """``paths``: each program's instructions' op_name paths
    (``program_paths``); without it no operation carries a scope."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[xtrace.Event] = []
    host: List[xtrace.Event] = []
    program: List[Event] = []
    devices = 0
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and xtrace.OPS_LINE in lines:
            dev = devices
            devices += 1
            runs = []
            if xtrace.MODULES_LINE in lines:
                runs = list(xtrace._events(lines[xtrace.MODULES_LINE], dev))
            ops.extend(_ops(lines[xtrace.OPS_LINE], dev, runs, paths or {}))
            modules.extend(runs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    start, end = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    if e.name.startswith(xtrace.HOST_PREFIX):
                        host.append(xtrace.Event(e.name, start, end))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append(Event(span_name(e.name), start, end,
                                             args=_args(e.stats)))
    return Trace(ops, modules, host, devices, program)


def _ops(line, dev: int, runs: List[xtrace.Event], paths: Dict[str, Dict[str, str]]):
    """The line's operations, each with the op_name path of its
    instruction in the program whose execution holds it."""
    runs = sorted(runs, key=lambda m: m.start)
    starts = [m.start for m in runs]
    for e in xtrace._events(line, dev):
        i = bisect.bisect_right(starts, e.start) - 1
        inside = i >= 0 and e.end <= runs[i].end
        scope = paths.get(program_name(runs[i].name), {}).get(e.name, "") if inside else ""
        yield Event(e.name, e.start, e.end, dev, scope=scope)


def _args(stats) -> Tuple[Tuple[str, object], ...]:
    return tuple((k, v) for k, v in stats if not k.startswith("_"))


_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*?metadata=\{op_name="([^"]*)"',
                          re.M)


def instruction_paths(hlo_text: str) -> Dict[str, str]:
    """Each instruction's op_name path in a compiled program's HLO text:
    ``%fusion.3`` -> ``jit(serve_step)/while/body/closed_call/mlp/...``."""
    return dict(_INSTRUCTION.findall(hlo_text))


def program_paths(engine, calls: "ProgramCalls") -> Dict[str, Dict[str, str]]:
    """``instruction_paths`` of ``jit_serve_step`` and ``jit_prefill_step``,
    compiled again with the arguments ``calls`` saw. An instruction name
    that two prefill lengths give different paths is left out."""
    import jax

    from repro.serving.engine import make_prefill_step, make_serve_step

    def text(step, *args):
        with metadata_in_cache_key():
            return jax.jit(step).lower(engine.params, *args).compile().as_text()

    decode: Dict[str, str] = {}
    if calls.decode_args is not None:
        decode = instruction_paths(text(make_serve_step(engine.ctx), *calls.decode_args))
    seen: Dict[str, str] = {}
    clash = set()
    for batch in calls.prefills.values():
        step = make_prefill_step(engine.ctx, capacity=engine.max_len)
        for name, path in instruction_paths(text(step, batch)).items():
            if seen.setdefault(name, path) != path:
                clash.add(name)
    prefill = {k: v for k, v in seen.items() if k not in clash}
    return {DECODE_PROGRAM: decode, PREFILL_PROGRAM: prefill}


@contextlib.contextmanager
def metadata_in_cache_key():
    """For the compiles inside, key the persistent compile cache on the
    programs' metadata (named scopes, source lines) as well."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


class ProgramCalls:
    """A proxy of the serving engine that passes every call through and
    keeps the abstract arguments of the first decode and of the first
    prefill of each shape, for ``program_paths``."""

    def __init__(self, engine):
        self.engine, self.ctx = engine, engine.ctx
        self.batch, self.max_len = engine.batch, engine.max_len
        self.decode_args = None
        self.prefills: Dict[tuple, dict] = {}

    def prefill(self, tokens, extras=None):
        if extras is None and tokens.shape not in self.prefills:
            self.prefills[tokens.shape] = {"tokens": _abstract(tokens)}
        return self.engine.prefill(tokens, extras)

    def decode(self, cache, tokens):
        if self.decode_args is None:
            self.decode_args = (_abstract(cache), _abstract(tokens))
        return self.engine.decode(cache, tokens)


def _abstract(tree):
    """Shapes, dtypes and placements of a tree of arrays, which lower a
    program as the arrays would."""
    import jax

    def one(x):
        weak = bool(getattr(getattr(x, "aval", None), "weak_type", False))
        return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=weak,
                                    sharding=getattr(x, "sharding", None))

    return jax.tree.map(one, tree)


def span_name(text: str) -> str:
    """A span's name without the ``#key=value,...#`` that a profiler may
    append for its arguments."""
    return text.split("#", 1)[0]


def scope_of(path: str) -> str:
    """The first of ``SCOPES`` in an op_name path, else "other"."""
    return next((p for p in path.split("/") if p in SCOPES), OTHER)


def program_name(module: str) -> str:
    """``jit_serve_step(12)`` -> ``jit_serve_step``."""
    return re.sub(r"\(\d+\)$", "", module)


def idle_gaps_program(trace: Trace, lo: float, hi: float, top: int = 12) -> List[List]:
    """Idle seconds on device 0 by the program span over them (those spans
    do not nest); a part of a gap that no program span covers goes to the
    harness span over it as in ``xtrace.gaps_by_host``, the rest to "no
    host span"; largest first."""
    program = xtrace.clip(trace.program, lo, hi)
    harness = [h for h in xtrace.clip(trace.host, lo, hi) if h.name != xtrace.FRAME]
    totals: Dict[str, float] = collections.defaultdict(float)
    for s, t in xtrace.idle_gaps(trace, lo, hi):
        covered = []
        for p in program:
            a, b = max(s, p.start), min(t, p.end)
            if b > a:
                totals[p.name] += b - a
                covered.append((a, b))
        for a, b in _uncovered(s, t, covered):
            rest = b - a
            for h in harness:
                overlap = min(b, h.end) - max(a, h.start)
                if overlap > 0:
                    totals[h.name] += overlap
                    rest -= overlap
            if rest > 1e-12:
                totals["no host span"] += rest
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def _uncovered(s: float, t: float, covered) -> List[Tuple[float, float]]:
    out, at = [], s
    for a, b in xtrace.merge(covered):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t > at:
        out.append((at, t))
    return out


def device_scopes(trace: Trace, lo: float, hi: float) -> List[List]:
    """Device seconds of innermost operations on device 0 by program and
    scope (``jit_serve_step/kv_write``): the program is the ``XLA
    Modules`` execution the operation ran in, the scope ``scope_of`` its
    path; largest first."""
    totals: Dict[str, float] = collections.defaultdict(float)
    for op, module in _ops_in_modules(trace, lo, hi):
        totals[f"{program_name(module.name)}/{scope_of(op.scope)}"] += op.end - op.start
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]


def _ops_in_modules(trace: Trace, lo: float, hi: float, modules=None):
    """(innermost operation, the module execution that holds it) on device
    0 in [lo, hi]; ``modules`` limits the executions."""
    if modules is None:
        modules = [m for m in xtrace.clip(trace.modules, lo, hi) if m.device == 0]
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]
    for op in xtrace.leaves(e for e in xtrace.clip(trace.ops, lo, hi) if e.device == 0):
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= modules[i].end:
            yield op, modules[i]


# --- readings (each takes a ``reading.RunRecord`` whose trace came from ``load``)

def _spans(run, names) -> List[Event]:
    return [e for e in getattr(run.trace, "program", ())
            if e.name in names and run.trace_lo <= e.start and e.end <= run.trace_hi]


def logits_copy_ms(run) -> Optional[float]:
    """Median duration of ``serve.retire.copy`` in the traced window: one
    (B, 1, V) logits copy from the device to the host."""
    copies = _spans(run, (RETIRE_COPY,))
    if not copies:
        return None
    return 1e3 * statistics.median(e.end - e.start for e in copies)


def retire_host_ms(run) -> Optional[float]:
    """Seconds of ``serve.retire.sample``, ``serve.retire.book`` and
    ``serve.decode.dispatch`` over the count of ``serve.retire.sample`` in
    the traced window: host time per retire once the logits are in hand."""
    spans = _spans(run, RETIRE_HOST)
    retires = sum(1 for e in spans if e.name == RETIRE_SAMPLE)
    if not retires:
        return None
    return 1e3 * xtrace.seconds(spans) / retires


def kv_write_share(run) -> Optional[float]:
    """Device seconds of innermost operations under scope ``kv_write``
    inside ``jit_serve_step`` executions over those executions' device
    seconds, in %."""
    programs = run.programs(DECODE_PROGRAM)
    if not programs:
        return None
    kv = sum(op.end - op.start
             for op, _ in _ops_in_modules(run.trace, run.trace_lo, run.trace_hi, programs)
             if scope_of(getattr(op, "scope", "")) == KV_WRITE)
    if not kv:
        return None
    return 100.0 * kv / xtrace.seconds(programs)


READINGS = {
    "logits_copy_ms": logits_copy_ms,
    "retire_host_ms": retire_host_ms,
    "kv_write_share": kv_write_share,
}


def measure(cell, seed: int, seconds: float, trace: bool, trace_s: float,
            peaks: Optional[dict]) -> dict:
    """Serve one window of ``cell`` (the profiler on over its last
    ``trace_s`` seconds with ``trace``) and reduce it."""
    import shutil
    import tempfile

    import run

    trace_s = min(trace_s, seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    with metadata_in_cache_key():
        s = run.serve(cell, seed, seconds, trace_dir, wrap=ProgramCalls, trace_s=trace_s)
    w = s.window
    rec = reading.RunRecord(cell.config, cell.traffic, w, s.proxy, peaks)
    last = w.t1 - trace_s
    out = {
        "cell": cell.name, "seed": seed, "seconds": seconds, "trace": int(trace),
        "trace_s": trace_s, "tokens_per_s": reading.tokens_per_s(rec),
        "decodes_per_s": sum(1 for c in s.proxy.decodes if w.in_window(c[0])) / w.seconds,
        "decodes_per_s_last": sum(1 for c in s.proxy.decodes if last <= c[0] < w.t1)
        / trace_s,
    }
    if not trace:
        return out
    tr = load(xtrace.find_xplane(trace_dir), program_paths(s.engine, s.proxy.engine))
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec.trace = tr
    rec.trace_lo, rec.trace_hi = lo, hi = tr.host_span("bench.window")
    rec.trace_stop = w.trace_stop
    passes = sum(1 for e in tr.host if e.name == "bench.pass" and lo <= e.start < hi)
    out.update({
        "passes_per_s_traced": passes / (hi - lo),
        "metrics": {m.name: run.cells.layer_reader(m.name).read(rec)
                    for m in cell.per_layer},
        "readings": {name: f(rec) for name, f in READINGS.items()},
        "busy_s": xtrace.busy_s(tr, lo, hi),
        "window_s": hi - lo,
        "breakdown": {
            "device_ops": xtrace.top_ops(tr, lo, hi),
            "idle_gaps": xtrace.gaps_by_host(tr, lo, hi),
            "idle_gaps_program": idle_gaps_program(tr, lo, hi),
            "device_scopes": device_scopes(tr, lo, hi),
        },
    })
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("program_trace.py: needs a TPU", file=sys.stderr)
        return 2
    out = measure(run.cells.resolve(args.workload), args.seed, args.seconds,
                  bool(args.trace), run.TRACE_S, run.load_peaks(dev.device_kind))
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
