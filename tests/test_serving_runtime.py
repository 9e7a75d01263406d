"""Continuous-batching runtime: equal-length grouping, arrival admission,
slot refill, interval metrics, the concurrency→τ response (the knob was a
no-op before this runtime existed), and CORAL closed-loop over live
traffic."""

import jax
import numpy as np
import pytest

from repro.configs.registry import REGISTRY
from repro.configs.runtime import RunConfig
from repro.models import ApplyCtx, init_model_params
from repro.serving import (
    Request,
    ServingController,
    ServingEngine,
    ServingRuntime,
    measure_runtime_throughput,
    workload,
)

VOCAB = 512  # reduced() clamps qwen2.5-3b's vocab to this


@pytest.fixture(scope="module")
def engine():
    cfg = REGISTRY["qwen2.5-3b"].reduced()
    rcfg = RunConfig(remat="none", moe_impl="dense")
    ctx = ApplyCtx(cfg, rcfg, None)
    params = init_model_params(jax.random.PRNGKey(0), cfg, rcfg)
    eng = ServingEngine(ctx, params, batch_size=2, max_len=64)
    # compile the prompt shapes the module's tests use
    measure_runtime_throughput(eng, 1, prompt_len=8, new_tokens=2, groups=1)
    measure_runtime_throughput(eng, 1, prompt_len=12, new_tokens=2, groups=1)
    return eng


def _req(rid, length, n=4, arrival=None, seed=None):
    rng = np.random.default_rng(length if seed is None else seed)
    return Request(rid, rng.integers(0, VOCAB, length, dtype=np.int32), n,
                   arrival_s=arrival)


def test_drain_serves_all_with_partial_groups(engine):
    rt = ServingRuntime(engine, concurrency=2)
    for rid in range(5):  # odd count -> one partial group
        rt.submit(_req(rid, 8, n=3))
    m = rt.drain()
    assert m["requests"] == 5 and m["queue_depth"] == 0
    assert m["throughput_tok_s"] > 0
    assert m["p99_latency_s"] >= m["p50_latency_s"]
    assert all(r.output.size == 3 for r in rt.done)


def test_equal_length_grouping_preserves_long_prompts(engine):
    """Old scheduler clipped every request to the group head's prompt
    length — a longer prompt arriving behind a shorter one was silently
    truncated. Groups are now equal-length, so the output of a request
    must not depend on what it queued behind."""
    long_req = _req(0, 12, n=4, seed=7)
    solo = ServingRuntime(engine, concurrency=1)
    solo.submit(Request(0, long_req.prompt.copy(), 4))
    solo.drain()
    ref = solo.done[0].output

    rt = ServingRuntime(engine, concurrency=1)
    rt.submit(_req(1, 8, n=4, seed=3))  # shorter request at the head
    rt.submit(Request(2, long_req.prompt.copy(), 4))
    rt.drain()
    got = next(r for r in rt.done if r.rid == 2).output
    np.testing.assert_array_equal(got, ref)


def test_arrival_admission_honors_trace_offsets(engine):
    rt = ServingRuntime(engine, concurrency=1)
    rt.submit(_req(0, 8, n=2, arrival=0.0))
    rt.submit(_req(1, 8, n=2, arrival=0.4))
    m = rt.drain()
    assert m["requests"] == 2
    late = next(r for r in rt.done if r.rid == 1)
    assert late.started - rt._t0 >= 0.4  # not prefilled before it "arrived"


def test_run_for_interval_and_window_metrics(engine):
    rt = ServingRuntime(engine, concurrency=2, window_s=1.0)
    for r in workload.steady(rate=40, duration_s=2.0, prompt_lens=8,
                             new_tokens=4, vocab=VOCAB):
        rt.submit(r)
    m = rt.run_for(0.4, idle_wait=True)
    assert m["interval_s"] == pytest.approx(0.4, abs=0.15)
    assert m["throughput_tok_s"] > 0
    w = rt.metrics_window()
    assert w["throughput_tok_s"] > 0 and "queue_depth" in w


def test_workload_generators_shapes_and_rates():
    for gen, kw in (
        (workload.steady, {}),
        (workload.bursty_poisson, {"burst_factor": 5.0}),
        (workload.diurnal, {"period_s": 2.0}),
    ):
        reqs = gen(rate=50.0, duration_s=4.0, prompt_lens=(8, 12),
                   new_tokens=(2, 6), vocab=128, seed=2, **kw)
        assert reqs, gen.__name__
        arr = np.array([r.arrival_s for r in reqs])
        assert (np.diff(arr) >= 0).all() and arr.max() < 4.0
        # mean rate within a loose factor of nominal
        assert 0.4 * 50 * 4 < len(reqs) < 2.0 * 50 * 4, (gen.__name__, len(reqs))
        assert all(r.prompt.size in (8, 12) for r in reqs)
        assert all(2 <= r.max_new_tokens <= 6 for r in reqs)
        assert all(r.prompt.max() < 128 for r in reqs)


def _drain_counting_passes(rt, n_requests):
    """Step ``rt`` until ``n_requests`` are done; returns the ring passes
    it took. Counts, not wall time: the same on any host."""
    passes = 0
    while len(rt.done) < n_requests:
        assert rt.step(), "runtime stalled with requests outstanding"
        passes += 1
    return passes


def test_concurrency_raises_measured_throughput(engine):
    """The acceptance property, as counts the runtime makes: the same
    saturating workload (8 groups × 4 tokens) takes the same decode
    retires at every c, and c in-flight slots retire more of them per
    ring pass — strictly more at c=2, ≥20% more at the best c — but
    never more than c per pass."""
    cs = (1, 2, 3, 4, 5)
    depth = {}
    for c in cs:
        rt = ServingRuntime(engine, concurrency=c)
        for rid in range(8 * rt.batch):
            rt.submit(_req(rid, 8, n=4))
        passes = _drain_counting_passes(rt, 8 * rt.batch)
        assert rt.steps == 8 * 4, (c, rt.steps)
        depth[c] = rt.steps / passes
        assert depth[c] <= c, depth
    assert depth[2] > depth[1], depth
    assert max(depth[c] for c in cs[1:]) >= 1.2 * depth[1], depth


@pytest.fixture(scope="module")
def second_engine():
    """A second registry model (distinct weights/shape from qwen2.5-3b)
    for the co-serving tests."""
    cfg = REGISTRY["hymba-1.5b"].reduced()
    rcfg = RunConfig(remat="none", moe_impl="dense")
    ctx = ApplyCtx(cfg, rcfg, None)
    params = init_model_params(jax.random.PRNGKey(1), cfg, rcfg)
    eng = ServingEngine(ctx, params, batch_size=2, max_len=64)
    measure_runtime_throughput(eng, 1, prompt_len=8, new_tokens=2, groups=1)
    return eng


def test_two_registry_models_served_with_isolated_metrics(engine, second_engine):
    """Two registry models co-served through per-tenant rings: each ring's
    windowed metrics see only its own traffic (a burst on one tenant never
    lands in the neighbour's record), the per-tenant τ are measurably
    distinct, and the aggregate view still adds up."""
    rt = ServingRuntime(engine, concurrency=2, window_s=4.0)
    rt.add_tenant("hymba", engine=second_engine, slots=1, tau_floor=1.0)
    # asymmetric load: a burst for the default tenant, a trickle for hymba
    for rid in range(8):
        rt.submit(_req(rid, 8, n=4))
    for rid in range(2):
        rt.submit(_req(100 + rid, 8, n=4), tenant="hymba")
    m = rt.drain()
    assert m["requests"] == 10 and m["queue_depth"] == 0
    tm = rt.tenant_metrics()
    assert set(tm) == {"default", "hymba"}
    assert tm["default"]["requests"] == 8
    assert tm["hymba"]["requests"] == 2
    assert tm["hymba"]["tau_floor"] == 1.0
    # every completion is tagged with its ring; neither pool leaked
    assert all(r.tenant == "default" for r in rt.ring().done)
    assert all(r.tenant == "hymba" for r in rt.ring("hymba").done)
    # distinct per-tenant token counts: different load, each ring
    # decoded exactly its own requests' tokens
    assert sum(r.output.size for r in rt.ring().done) == 8 * 4
    assert sum(r.output.size for r in rt.ring("hymba").done) == 2 * 4
    assert tm["default"]["throughput_tok_s"] > 0
    assert tm["hymba"]["throughput_tok_s"] > 0


def test_attribute_power_sums_exactly_to_rail(engine, second_engine):
    rt = ServingRuntime(engine, concurrency=1, window_s=4.0)
    rt.add_tenant("hymba", engine=second_engine, slots=1)
    for rid in range(4):
        rt.submit(_req(rid, 8, n=4))
    for rid in range(2):
        rt.submit(_req(100 + rid, 8, n=4), tenant="hymba")
    rt.drain()
    total = 7.3
    att = rt.attribute_power(total)
    assert set(att) == {"default", "hymba"}
    assert sum(att.values()) == total  # exact, not approx — one rail
    assert att["default"] > att["hymba"] > 0  # weighted by window tokens
    # empty window (no traffic yet): equal split, still exact
    idle = ServingRuntime(engine, concurrency=1)
    idle.add_tenant("hymba", engine=second_engine)
    att0 = idle.attribute_power(total)
    assert sum(att0.values()) == total
    assert att0["default"] == pytest.approx(att0["hymba"])


def test_slot_allocation_shifts_tenant_throughput(engine):
    """The live slot knob is a genuine resource split: with 3-vs-1 slots
    the favored tenant finishes its requests in fewer ring passes than
    with 1-vs-3, under saturating load on both rings (a count of the
    runtime's passes, not wall time)."""

    def passes_to_finish(slots0, slots1):
        rt = ServingRuntime(engine, concurrency=slots0, window_s=4.0)
        rt.add_tenant("b", engine=engine, slots=slots1)
        for rid in range(10):
            rt.submit(_req(rid, 8, n=4))
            rt.submit(_req(100 + rid, 8, n=4), tenant="b")
        rt.set_slot_allocation({"default": slots0, "b": slots1})
        return _drain_counting_passes(rt, 10)

    assert passes_to_finish(3, 1) < passes_to_finish(1, 3)


def test_multitenant_controller_tunes_joint_headroom(engine, second_engine):
    """Closed loop over a cotenant space: per-tenant slot dims are enacted
    on the rings, feedback is the joint headroom against the rings' τ
    floors, and the records carry the per-tenant split."""
    from repro.core.space import cotenant_space, tenant_slot_indices
    from repro.device.hw import get_profile

    cap = measure_runtime_throughput(engine, 2, prompt_len=8, new_tokens=8,
                                     groups=4)
    rt = ServingRuntime(engine, concurrency=1)
    rt.ring().tau_floor = 0.10 * cap
    rt.add_tenant("hymba", engine=second_engine, slots=1,
                  tau_floor=0.05 * cap)
    space = cotenant_space("edge_xavier_nx", 2)
    new_tokens = 8
    iters, interval = 4, 0.4
    tr0 = workload.steady(rate=0.2 * cap / new_tokens,
                          duration_s=iters * interval + 1.0, prompt_lens=8,
                          new_tokens=new_tokens, vocab=VOCAB, seed=1)
    tr1 = workload.steady(rate=0.1 * cap / new_tokens,
                          duration_s=iters * interval + 1.0, prompt_lens=8,
                          new_tokens=new_tokens, vocab=VOCAB, seed=2)
    for i, r in enumerate(tr1):
        r.tenant = "hymba"
        r.rid = 10000 + i
    trace = sorted(tr0 + tr1, key=lambda r: r.arrival_s)
    ctrl = ServingController(
        rt, space, trace, tau_target=1.0, p_budget=1e9,
        interval_s=interval, hw=get_profile("edge-xavier-nx").hw,
    )
    outcome, records = ctrl.run(iters)
    assert len(records) == iters
    slot_idx = tenant_slot_indices(space)
    for rec in records:
        assert set(rec.tenant_taus) == {"default", "hymba"}
        # the τ channel is the scalarized joint headroom, not raw tok/s
        floors = [rt.ring().tau_floor, rt.ring("hymba").tau_floor]
        taus = [rec.tenant_taus["default"], rec.tenant_taus["hymba"]]
        assert rec.tau == pytest.approx(min(t / f for t, f in zip(taus, floors)))
    # the slot knobs were genuinely applied across intervals
    assert len({tuple(r.config[i] for i in slot_idx) for r in records}) > 1


def test_cotenant_controller_requires_floors_and_matching_rings(engine):
    from repro.core.space import cotenant_space

    space = cotenant_space("edge_xavier_nx", 2)
    rt = ServingRuntime(engine, concurrency=1)  # one ring, two slot dims
    with pytest.raises(ValueError, match="tenant rings"):
        ServingController(rt, space, [], tau_target=1.0)
    rt.add_tenant("b", engine=engine)  # floors unset (0.0)
    with pytest.raises(ValueError, match="tau_floor"):
        ServingController(rt, space, [], tau_target=1.0)


def test_closed_loop_coral_finds_feasible_under_bursty_trace(engine):
    from repro.core import tpu_pod_space
    from repro.device.measure import analytic_scale_and_power

    space = tpu_pod_space()
    cap = measure_runtime_throughput(engine, 5, prompt_len=8, new_tokens=16,
                                     groups=8)
    new_tokens = 8
    iters, interval_s = 8, 0.4
    trace = workload.bursty_poisson(
        rate=0.5 * cap / new_tokens, duration_s=iters * interval_s + 2.0,
        prompt_lens=8, new_tokens=new_tokens, vocab=VOCAB, seed=1,
    )
    tau_target = 0.25 * cap
    p_budget = analytic_scale_and_power(
        space.names, space.preset("max_power"))[1] * 0.9
    controller = ServingController(
        ServingRuntime(engine, concurrency=1), space, trace,
        tau_target=tau_target, p_budget=p_budget, interval_s=interval_s,
    )
    outcome, records = controller.run(iters)
    assert len(records) == iters
    assert outcome.config is not None
    assert outcome.feasible(tau_target, p_budget), [
        (r.config, r.tau, r.power) for r in records
    ]
    # the knob was genuinely applied: the runtime ran at the proposed
    # concurrency levels, not a fixed one
    assert len({int(r.config[-1]) for r in records}) > 1
