"""Schedule capture & deterministic replay (repro/core/trace.py).

Differential harness for the trace subsystem:

  (a) a SimBackend trace replays through the sim side bit-identically —
      same ops_log, restore_finish and busy fractions — including across a
      JSON round trip and with an injected channel failure;
  (b) the SAME trace replays through the RealBackend side: every dispatched
      op executes on device under the captured interleaving and every
      request's restored cache verifies against its full-prefill ground
      truth (the channel-failure incident re-executes its aborted transfer);
  (c) sim and real replays of one trace agree on dispatch ORDER when
      durations are pinned — the schedule is backend-invariant.

Plus: replay divergence detection, determinism property tests, and
regression tests for the stage-blocked dispatch starvation fix and the
zero-plan strict error.
"""
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import (CostModel, EngineBackend, EngineCore, EngineRequest,
                        ReplayDivergence, RestorationExecutor, ScheduleTrace,
                        SimBackend, TraceRecorder, capture, replay_trace)
from repro.core.baselines import make_baseline_plans
from repro.core.plans import RequestPlan
from repro.models import build_model

RNG = jax.random.PRNGKey(0)
LENS = {"a": 40, "b": 24, "c": 32}


def _executor(stages=2, chunk=8, lens=LENS):
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    ex = RestorationExecutor(m, params, chunk_size=chunk, stages=stages)
    for rid, n in lens.items():
        inputs = jax.random.randint(RNG, (1, n), 0, cfg.vocab_size) \
            if cfg.input_mode == "tokens" else \
            jax.random.normal(RNG, (1, n, cfg.d_model), jnp.float32)
        ex.remember(rid, inputs)
    return cfg, ex


def _requests(cfg, lens=LENS, *, chunk=8, bounds=None, arrivals=None):
    arrivals = arrivals or {}
    return [EngineRequest(rid, n, arrivals.get(rid, 0.0),
                          make_baseline_plans("cacheflow", rid, n,
                                              chunk_size=chunk, l_delta=16,
                                              num_layers=cfg.num_layers,
                                              stage_bounds=bounds))
            for rid, n in lens.items()]


def _sim_capture(cfg, *, bounds, fail=False, io_channels=2, stages=2):
    """Capture a >=3-request SimBackend trace on the reduced-model geometry;
    with ``fail=True`` a channel dies mid-transfer (abort guaranteed by
    picking the failure time inside a dry-run transfer interval)."""
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    kw = dict(stages=stages, io_channels=io_channels, strict=True)
    fail_at = None
    if fail:
        dry = EngineCore(SimBackend(cost, benefit_gate=False), **kw) \
            .run(_requests(cfg, bounds=bounds))
        t0, t1 = next((t0, t1) for t0, t1, res, _ in dry.ops_log
                      if res == "io1")
        fail_at = {1: (t0 + t1) / 2}
    core = EngineCore(SimBackend(cost, benefit_gate=False),
                      channel_fail_at=fail_at, **kw)
    res, trace = capture(core, _requests(cfg, bounds=bounds))
    assert set(res.restore_finish) == set(LENS)
    if fail:
        assert trace.aborts(), "failure was injected but nothing aborted"
    return res, trace


# ---------------------------------------------------------------------------
# (a) sim -> sim: bit-identical replay, JSON round trip, failure incidents
# ---------------------------------------------------------------------------


def test_sim_replay_bit_identical():
    cfg = get_config("qwen3-8b").reduced()
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    res, trace = _sim_capture(cfg, bounds=bounds)
    rep = replay_trace(trace)
    assert rep == res                       # whole EngineResult, bit-exact
    assert rep.ops_log == res.ops_log
    assert rep.restore_finish == res.restore_finish
    assert rep.compute_busy == res.compute_busy
    assert rep.io_busy == res.io_busy


def test_sim_replay_bit_identical_after_json_round_trip(tmp_path):
    cfg = get_config("qwen3-8b").reduced()
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    res, trace = _sim_capture(cfg, bounds=bounds, fail=True)
    path = tmp_path / "trace.json"
    trace.save(str(path))
    loaded = ScheduleTrace.load(str(path))
    assert loaded == trace                  # lossless serialization
    rep = replay_trace(loaded)
    assert rep == res
    assert rep == loaded.captured_result()


def test_sim_replay_with_failure_incident_bit_identical():
    """An injected channel failure (aborted + re-dispatched transfer) is part
    of the captured schedule and replays exactly."""
    cfg = get_config("qwen3-8b").reduced()
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    res, trace = _sim_capture(cfg, bounds=bounds, fail=True)
    op = trace.aborts()[0].op
    redispatched = [e for e in trace.dispatches() if e.op == op]
    assert len(redispatched) >= 2           # aborted once, re-executed
    assert replay_trace(trace) == res


# ---------------------------------------------------------------------------
# (b) sim -> real: the captured interleaving executes on device and every
#     cache verifies against full-prefill ground truth (tentpole acceptance)
# ---------------------------------------------------------------------------


def test_sim_trace_replays_through_real_backend_with_verification():
    cfg, ex = _executor(stages=2)
    sim_res, trace = _sim_capture(cfg, bounds=ex.bounds, fail=True)
    rep = replay_trace(trace, ex, verify=True)   # verify raises on mismatch
    assert set(rep.restore_finish) == set(LENS)
    for rid in LENS:
        ex.verify(rid)                           # bit-exact per-request cache
    # the real replay executed the EXACT captured interleaving
    assert rep.ops_log == sim_res.ops_log
    assert rep.restore_finish == sim_res.restore_finish


def test_real_capture_replays_through_real_backend():
    """real -> real: a trace captured from on-device execution re-executes
    deterministically (pinned measured durations) and still verifies."""
    cfg, ex = _executor(stages=2)
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    from repro.core import RealBackend
    core = EngineCore(RealBackend(ex), stages=2, io_channels=2, strict=True)
    res, trace = capture(core, _requests(cfg, bounds=ex.bounds))
    cfg2, ex2 = _executor(stages=2)
    rep = replay_trace(trace, ex2, verify=True)
    assert rep.ops_log == res.ops_log
    for rid in LENS:
        ex2.verify(rid)


# ---------------------------------------------------------------------------
# (c) sim <-> real dispatch-order parity under pinned durations
# ---------------------------------------------------------------------------


def test_sim_and_real_replays_dispatch_in_identical_order():
    cfg, ex = _executor(stages=2)
    _, trace = _sim_capture(cfg, bounds=ex.bounds, fail=True)
    rec_sim, rec_real = TraceRecorder(), TraceRecorder()
    res_sim = replay_trace(trace, trace_out=rec_sim)
    res_real = replay_trace(trace, ex, verify=True, trace_out=rec_real)
    key = lambda e: (e.resource, e.op["kind"], e.op["request_id"],
                     e.op["stage"], e.op["unit"])
    assert [key(e) for e in rec_sim.trace.dispatches()] == \
           [key(e) for e in rec_real.trace.dispatches()]
    assert res_sim.ops_log == res_real.ops_log
    assert res_sim.restore_finish == res_real.restore_finish


# ---------------------------------------------------------------------------
# Divergence detection
# ---------------------------------------------------------------------------


def test_replay_divergence_raises():
    cfg = get_config("qwen3-8b").reduced()
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    _, trace = _sim_capture(cfg, bounds=bounds)
    # tamper: swap two different recorded dispatches -> op identity mismatch
    d = trace.dispatches()
    i, j = 0, next(k for k, e in enumerate(d) if e.op != d[0].op)
    d[i].op, d[j].op = d[j].op, d[i].op
    with pytest.raises(ReplayDivergence, match="diverged"):
        replay_trace(trace)


def test_replay_rejects_truncated_trace():
    cfg = get_config("qwen3-8b").reduced()
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    _, trace = _sim_capture(cfg, bounds=bounds)
    cut = trace.dispatches()[len(trace.dispatches()) // 2]
    trace.events = trace.events[:trace.events.index(cut)]
    with pytest.raises(ReplayDivergence, match="past the end"):
        replay_trace(trace, strict=False)


# ---------------------------------------------------------------------------
# Determinism properties (seeded; hypothesis when available)
# ---------------------------------------------------------------------------


def _seeded_requests(cfg, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = {f"r{i}": int(rng.integers(600, 6000))
            for i in range(int(rng.integers(3, 7)))}
    arrivals = {rid: float(rng.uniform(0, 0.01)) for rid in lens}
    return [EngineRequest(rid, n, arrivals[rid],
                          make_baseline_plans("cacheflow", rid, n,
                                              chunk_size=256, l_delta=1000,
                                              num_layers=cfg.num_layers))
            for rid, n in lens.items()]


@pytest.mark.property
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_identical_seeds_give_identical_results_and_replays(seed):
    """Same seed -> bit-identical ops_log/EngineResult across repeated
    SimBackend runs; the captured trace replays to the same result; the
    trace JSON round-trips losslessly."""
    cfg = get_config("qwen3-8b")
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    kw = dict(stages=1, io_channels=2, max_active=3, strict=True)
    res1, trace1 = capture(EngineCore(SimBackend(cost), **kw),
                           _seeded_requests(cfg, seed))
    res2, trace2 = capture(EngineCore(SimBackend(cost), **kw),
                           _seeded_requests(cfg, seed))
    assert res1 == res2
    assert res1.ops_log == res2.ops_log
    assert trace1 == trace2
    round_tripped = ScheduleTrace.from_json(trace1.to_json())
    assert round_tripped == trace1
    assert replay_trace(round_tripped) == res1


# ---------------------------------------------------------------------------
# Regression: stage-blocked head must not starve other requests (sequential
# ablation), and zero-plan requests fail cleanly under strict.
# ---------------------------------------------------------------------------


class _ConstBackend(EngineBackend):
    def compute_secs(self, op, req):
        return 1.0

    def io_secs(self, op, req, bandwidth):
        return 0.1


def _two_stage_starvation_requests():
    # "a": compute-only, 4 chunks per stage -> occupies comp0 for 4s, its
    # stage-1 ops are blocked (sequential ablation) until t=4.
    a = [RequestPlan("a", 32, 8, "token", 0, 2, stage=0),
         RequestPlan("a", 32, 8, "token", 2, 4, stage=1)]
    for p in a:
        p.plan.io_enabled = False
    # "b": stage 0 restored by one fast load (t=0.1); its single stage-1
    # compute chunk is then runnable while "a" still grinds stage 0.
    b = [RequestPlan("b", 8, 8, "token", 0, 2, stage=0),
         RequestPlan("b", 8, 8, "token", 2, 4, stage=1)]
    b[0].plan.comp_enabled = False
    b[1].plan.io_enabled = False
    return [EngineRequest("a", 32, 0.0, a), EngineRequest("b", 8, 0.0, b)]


def test_stage_blocked_head_does_not_starve_other_requests():
    core = EngineCore(_ConstBackend(), stages=2, io_channels=1,
                      stage_parallel=False, strict=True)
    res = core.run(_two_stage_starvation_requests())
    # before the fix, b's stage-1 chunk was stranded behind a's blocked head
    # until a finished stage 0 AND stage 1 (finish ~9.0); with blocked
    # requests skipped it dispatches right after b's stage-0 load.
    assert res.restore_finish["b"] == pytest.approx(1.1)
    assert res.restore_finish["a"] == pytest.approx(8.0)
    # b's stage-1 compute overlaps a's stage-0 window in the log
    b_comp1 = next(t0 for t0, _, r, d in res.ops_log
                   if r == "comp1" and d.startswith("b:"))
    assert b_comp1 < 4.0


def test_strict_raises_cleanly_on_zero_plan_request():
    core = EngineCore(_ConstBackend(), stages=1, strict=True)
    with pytest.raises(ValueError, match="zero plans"):
        core.run([EngineRequest("empty", 10)])
    # non-strict: plan-less requests are dropped, the rest still run
    core = EngineCore(_ConstBackend(), stages=1)
    ok = [RequestPlan("ok", 8, 8, "token", 0, 2, stage=0)]
    res = core.run([EngineRequest("empty", 10), EngineRequest("ok", 8, 0.0, ok)])
    assert set(res.restore_finish) == {"ok"}


def test_engine_request_default_plans_not_shared():
    r1, r2 = EngineRequest("x", 1), EngineRequest("y", 1)
    assert r1.plans == [] and r1.plans is not r2.plans
