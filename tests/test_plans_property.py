"""Property tests (hypothesis) on the paper's scheduling invariants."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.cost_model import CostModel
from repro.core.plans import TwoPointerPlan, make_request_plans
from repro.core.scheduler import BatchScheduler
from repro.config import HARDWARE, ModelConfig

CFG = ModelConfig(name="t", family="dense", num_layers=8, d_model=256,
                  num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512,
                  vocab_size=1024)


# ---------------------------------------------------------------------------
# TwoPointerPlan invariants: pointers never cross, every unit exactly once
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=200, deadline=None)
@given(n=st.integers(1, 40), seed=st.integers(0, 2**31 - 1),
       io_on=st.booleans(), comp_on=st.booleans())
def test_two_pointer_exact_coverage(n, seed, io_on, comp_on):
    if not io_on and not comp_on:
        comp_on = True
    plan = TwoPointerPlan(n, comp_enabled=comp_on, io_enabled=io_on)
    rng = np.random.default_rng(seed)
    restored = []
    guard = 0
    while not plan.done:
        guard += 1
        assert guard < 10 * n + 10, "livelock"
        if rng.random() < 0.5:
            u = plan.claim_compute()
            if u is not None:
                plan.complete_compute(u)
                restored.append(u)
        else:
            u = plan.claim_io()
            if u is not None:
                plan.complete_io(u)
                restored.append(u)
    # every unit exactly once
    assert sorted(restored) == list(range(n))
    # pointers never crossed: compute prefix and io suffix are disjoint
    assert plan.comp_done + plan.io_done == n


@pytest.mark.property
@settings(max_examples=100, deadline=None)
@given(n=st.integers(1, 30), seed=st.integers(0, 2**31 - 1))
def test_inflight_units_never_collide(n, seed):
    plan = TwoPointerPlan(n)
    rng = np.random.default_rng(seed)
    guard = 0
    while not plan.done and guard < 500:
        guard += 1
        c = plan.claim_compute() if rng.random() < 0.7 else None
        i = plan.claim_io() if rng.random() < 0.7 else None
        if c is not None and i is not None:
            assert c != i
        if c is not None:
            plan.complete_compute(c)
        if i is not None:
            plan.complete_io(i)


# ---------------------------------------------------------------------------
# Batch scheduler: coverage across requests; policy sanity
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=50, deadline=None)
@given(lengths=st.lists(st.integers(100, 30_000), min_size=1, max_size=6),
       seed=st.integers(0, 2**31 - 1),
       policy=st.sampled_from(["longest_remaining", "fifo", "shortest_remaining"]))
def test_batch_scheduler_completes_everything(lengths, seed, policy):
    sched = BatchScheduler(io_policy=policy)
    for i, n in enumerate(lengths):
        sched.add_request(make_request_plans(f"r{i}", n, chunk_size=512,
                                             l_delta=4096, num_layers=8))
    rng = np.random.default_rng(seed)
    guard = 0
    while not sched.all_done():
        guard += 1
        assert guard < 10_000
        progressed = False
        if rng.random() < 0.5:
            op = sched.next_io()
            if op:
                sched.complete(op)
                progressed = True
        op = sched.next_compute(stage=0)
        if op:
            sched.complete(op)
            progressed = True
        if not progressed:
            op = sched.next_io()
            if op:
                sched.complete(op)
                progressed = True
        assert progressed or sched.all_done()
    for i in range(len(lengths)):
        assert sched.request_done(f"r{i}")


def test_longest_remaining_priority():
    """Operationalised §3.3 policy: the compute-head request's transfers are
    critical-path-first; surplus channel capacity prefetches the request with
    the LARGEST remaining restoration (not FIFO)."""
    sched = BatchScheduler(io_policy="longest_remaining")
    sched.add_request(make_request_plans("head", 1000, chunk_size=100,
                                         l_delta=0, num_layers=8))
    sched.add_request(make_request_plans("mid", 5000, chunk_size=100,
                                         l_delta=0, num_layers=8))
    sched.add_request(make_request_plans("long", 10_000, chunk_size=100,
                                         l_delta=0, num_layers=8))
    op1 = sched.next_io()
    assert op1.request_id == "head"          # critical path first
    op2 = sched.next_io()                    # head busy -> longest prefetch
    assert op2.request_id == "long"


# ---------------------------------------------------------------------------
# Harmonic-mean bound (Eq. 1): two-pointer optimum <= any static split
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=50, deadline=None)
@given(n=st.integers(1_000, 40_000), bw_gbps=st.floats(1.0, 100.0),
       mfu=st.floats(0.2, 0.9))
def test_token_split_beats_static_splits(n, bw_gbps, mfu):
    cost = CostModel(CFG, HARDWARE["tpu_v5e"], bw_gbps * 1e9 / 8, mfu=mfu)
    t_opt = cost.t_token_wise(n)
    # optimal two-pointer beats any static split, up to one chunk's fixed
    # overhead (the split is chunk-quantised)
    slack = cost.hw.kernel_overhead_s + 1e-9
    for frac in (0.0, 0.25, 0.5, 0.75, 1.0):
        k = int(n * frac)
        t_static = max(cost.t_comp(k), cost.t_io_tokens(n - k))
        assert t_opt <= t_static + slack
    # and the harmonic bound lower-bounds both pure strategies (Eq. 1)
    assert cost.harmonic_bound(n) <= min(cost.t_comp(n), cost.t_io_tokens(n)) + 1e-9


@pytest.mark.property
@settings(max_examples=30, deadline=None)
@given(n=st.integers(2_000, 40_000), stages=st.integers(1, 8))
def test_stage_parallel_linear_speedup(n, stages):
    cost = CostModel(CFG, HARDWARE["tpu_v5e"], 10e9 / 8)
    t1 = cost.stage_parallel_bound(n, 1)
    ts = cost.stage_parallel_bound(n, stages)
    np.testing.assert_allclose(ts, t1 / stages, rtol=1e-9)  # Eq. 2


@pytest.mark.property
@settings(max_examples=30, deadline=None)
@given(bw=st.floats(1.0, 200.0), mfu=st.floats(0.2, 0.9))
def test_l_delta_crossover_is_stable(bw, mfu):
    """Fig. 3: a crossover exists and once token-wise wins it KEEPS winning
    for longer prefixes (the quadratic recompute skew only grows)."""
    c = CostModel(CFG, HARDWARE["tpu_v5e"], bw * 1e9 / 8, mfu=mfu)
    ld = c.crossover_l_delta(max_n=32768)
    assert 128 <= ld <= 32768
    if ld <= 8192:
        # one kernel-launch of absolute slack: at tiny scales both strategies
        # are fixed-overhead dominated and the comparison is launch noise
        assert c.t_token_wise(4 * ld) <= (c.t_layer_wise(4 * ld) * 1.1
                                          + c.hw.kernel_overhead_s)
