"""Preemption-safe engine core: restoration preempt/resume under admission
pressure, plus the contention-blind benefit-gate / abort-accounting fixes.

  * Policy: under ``preempt="priority"`` a higher-priority arrival that
    finds ``max_active`` full suspends the still-restoring victim with the
    smallest remaining restoration benefit instead of queueing; the victim
    resumes on a freed slot with every completed unit intact (resume, not
    restart — EngineResult accounting proves it).
  * Invariants (property test): across randomized interleavings and
    preempt/resume cycles every unit is restored exactly once, no claim
    leaks, and phase transitions stay monotone.
  * Real mode: a preempted-then-resumed request's restored cache verifies
    bit-exactly and its first-token logits + greedy decode outputs match
    the no-preemption full-prefill reference.
  * Trace schema v3: preempt/resume events round-trip and replay
    bit-identically; v2 (pre-preemption) traces still load.
  * Gate fix: the marginal-benefit gate prices transfers at the candidate
    channel's EFFECTIVE bandwidth — a degraded channel flips the decision.
  * Abort fix: aborted transfers are excluded from ``io_busy`` and tagged
    ``:aborted`` in ``ops_log``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _engine_helpers import RngBackend
from hypothesis import given, settings, strategies as st

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import (CostModel, EngineCore, EngineRequest,
                        RealBackend, RestorationExecutor, ScheduleTrace,
                        SimBackend, capture, interleaving_dur_fn, replay_trace)
from repro.core.baselines import make_baseline_plans
from repro.core.plans import make_request_plans
from repro.core.trace import TRACE_VERSION
from repro.models import build_model
from repro.models.kvcache import grow_cache
from repro.serving import RealServingEngine, Request, SimServingEngine
from repro.serving.workloads import bursty_priority

RNG = jax.random.PRNGKey(0)


def _cost(arch="qwen3-8b", hw="h100", bw="10Gbps"):
    return CostModel(get_config(arch), HARDWARE[hw], IO_BANDWIDTHS[bw], mfu=0.45)


def _req(cfg, rid, n, arrival=0.0, prio=0, new=128, dec=8, chunk=512):
    plans = make_baseline_plans("cacheflow", rid, n, chunk_size=chunk,
                                l_delta=0, num_layers=cfg.num_layers)
    return EngineRequest(rid, n, arrival, plans, new_len=new, decode_len=dec,
                         priority=prio)


def _burst(cfg):
    """Two long low-priority restorations saturate max_active=2; a burst of
    two short high-priority requests lands mid-restoration."""
    return [_req(cfg, "bg0", 30_000), _req(cfg, "bg1", 28_000),
            _req(cfg, "hi0", 1_000, 0.5, prio=1),
            _req(cfg, "hi1", 1_200, 0.5, prio=1)]


def _completed_restoration_units(res, rid):
    """Restoration ops of ``rid`` that ran to completion (aborted excluded)."""
    return sum(1 for *_, desc in res.ops_log
               if desc.startswith(f"{rid}:") and not desc.endswith(":aborted")
               and desc.split(":")[1][0] in "cl")


# ---------------------------------------------------------------------------
# Tentpole: priority preemption cuts high-priority TTFT; resume, not restart
# ---------------------------------------------------------------------------


def test_priority_preemption_reduces_high_priority_ttft():
    cost = _cost()
    cfg = cost.cfg
    results = {}
    for policy in ("none", "priority"):
        core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                          max_active=2, preempt=policy, strict=True)
        results[policy] = core.run(_burst(cfg))
    base, pre = results["none"], results["priority"]
    assert not base.preemptions and pre.preemptions
    hi = ("hi0", "hi1")
    ttft = lambda r: np.mean([r.first_token[h] - 0.5 for h in hi])
    # acceptance: high-priority mean TTFT drops, makespan regresses < 10%
    assert ttft(pre) < ttft(base) * 0.7
    assert pre.makespan < base.makespan * 1.10
    # resume, not restart: a preempted request's completed units are all
    # kept — the non-aborted restoration op count is EXACTLY its unit total
    for rid, count in pre.preemptions.items():
        assert count >= 1
        req = next(r for r in _burst(cfg) if r.request_id == rid)
        total_units = sum(p.plan.n_units for p in req.plans)
        assert _completed_restoration_units(pre, rid) == total_units


def test_preempted_victim_is_least_remaining_benefit():
    """Among eligible victims the engine suspends the one with the SMALLEST
    remaining restoration (least marginal recompute saving): bg1 is nearly
    done when the urgent request arrives, so bg1 — not bg0 — is paused."""
    cost = _cost()
    cfg = cost.cfg
    reqs = [_req(cfg, "bg0", 30_000), _req(cfg, "bg1", 6_000),
            _req(cfg, "hi0", 1_000, 0.5, prio=1)]
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, preempt="priority", strict=True)
    res = core.run(reqs)
    assert "bg1" in res.preemptions and "bg0" not in res.preemptions


def test_deadline_policy_preempts_later_deadline():
    cost = _cost()
    cfg = cost.cfg

    def mk():
        slack = _req(cfg, "slack", 20_000)
        slack.deadline = 500.0
        urgent = _req(cfg, "edf", 1_000, 0.5)
        urgent.deadline = 1.0
        return [slack, urgent]

    results = {}
    for policy in ("none", "deadline"):
        core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                          max_active=1, preempt=policy, strict=True)
        results[policy] = core.run(mk())
    res = results["deadline"]
    # the slack request (later deadline) is the victim, never the EDF winner
    assert res.preemptions == {"slack": 1}
    # EDF admission puts the urgent request far ahead of FCFS queueing
    ttft = lambda r: r.first_token["edf"] - 0.5
    assert ttft(res) < ttft(results["none"]) * 0.5
    # the suspended request still finishes, with all its units intact
    assert _completed_restoration_units(res, "slack") == \
        sum(p.plan.n_units for p in mk()[0].plans)


def test_preempt_none_keeps_fcfs_and_rejects_unknown_policy():
    cost = _cost()
    cfg = cost.cfg
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, preempt="none", strict=True)
    res = core.run(_burst(cfg))
    assert res.preemptions == {}
    # FCFS: the burst waits for a freed slot, after the earlier arrivals
    assert min(res.restore_start["hi0"], res.restore_start["hi1"]) \
        >= min(res.finish["bg0"], res.finish["bg1"])
    with pytest.raises(ValueError, match="preempt"):
        EngineCore(SimBackend(cost), preempt="sometimes")


def test_sim_engine_bursty_priority_acceptance():
    """End-to-end acceptance on the serving facade: bursty two-priority
    workload under max_active pressure — preempt="priority" cuts the
    high-priority mean TTFT while total makespan regresses < 10%."""
    cfg = get_config("qwen3-8b")
    reqs = bursty_priority(18, seed=3, burst_every=2.0, burst_size=3)
    reports = {}
    for policy in ("none", "priority"):
        eng = SimServingEngine(cfg, HARDWARE["h100"],
                               io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                               stages=1, max_batch=2, preempt=policy)
        reports[policy] = eng.run([Request(**{
            "request_id": r.request_id, "arrival": r.arrival,
            "prefix_len": r.prefix_len, "new_len": r.new_len,
            "decode_len": r.decode_len, "priority": r.priority,
            "deadline": r.deadline}) for r in reqs])
    base, pre = reports["none"], reports["priority"]
    assert sum(pre.preemptions.values()) > 0
    hi = [r.request_id for r in reqs if r.priority > 0]
    hi_mean = lambda rep: np.mean([rep.ttfts[h] for h in hi])
    e2e_end = lambda rep: max(rep.e2e[r.request_id] + r.arrival for r in reqs)
    assert hi_mean(pre) < hi_mean(base)
    assert e2e_end(pre) < e2e_end(base) * 1.10


# ---------------------------------------------------------------------------
# Property: preemption invariants under randomized interleavings
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_preemption_invariants_random_interleavings(seed):
    """Across preempt/resume cycles: every unit restored exactly once, no
    claim leaks, monotone phase transitions, nothing lost or restarted."""
    rng = np.random.default_rng(seed)
    stages = int(rng.integers(1, 3))
    bounds = [(0, 2), (2, 4)][:stages] if stages == 2 else [(0, 4)]
    policy = ["priority", "deadline"][int(rng.integers(0, 2))]
    reqs = []
    for i in range(int(rng.integers(4, 8))):
        n = int(rng.integers(16, 160))
        plans = make_request_plans(f"r{i}", n, chunk_size=8, l_delta=0,
                                   num_layers=4, stage_bounds=bounds,
                                   strategy="token")
        reqs.append(EngineRequest(
            f"r{i}", n, arrival=float(rng.uniform(0, 3.0)), plans=plans,
            new_len=int(rng.integers(0, 3)) * 16,
            decode_len=int(rng.integers(0, 5)),
            priority=int(rng.integers(0, 3)),
            deadline=float(rng.uniform(0.5, 20.0))))
    core = EngineCore(RngBackend(seed), stages=stages,
                      io_channels=int(rng.integers(1, 3)),
                      max_active=int(rng.integers(1, 4)),
                      preempt=policy, strict=True)
    res = core.run(reqs)
    for r in reqs:
        rid = r.request_id
        # lifecycle completed, monotone
        assert rid in res.restore_finish and rid in res.finish
        assert res.restore_start[rid] <= res.restore_finish[rid] \
            <= res.finish[rid]
        if r.new_len > 0 or r.decode_len > 0:
            assert res.restore_finish[rid] <= res.first_token[rid] \
                <= res.finish[rid]
        # no claim leaks, all plans done
        for p in r.plans:
            assert p.plan.done
            assert p.plan.comp_inflight is None and p.plan.io_inflight is None
            assert p.plan.comp_done + p.plan.io_done == p.plan.n_units
        # every unit restored EXACTLY once (preempted or not): completed
        # restoration ops == unit total; aborted ops are tagged separately
        total_units = sum(p.plan.n_units for p in r.plans)
        assert _completed_restoration_units(res, rid) == total_units


# ---------------------------------------------------------------------------
# Real mode: preempted-then-resumed request bit-matches the reference
# ---------------------------------------------------------------------------


def test_real_preempted_request_parity_vs_full_prefill_reference():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    eng = RealServingEngine(m, params, system="cacheflow", stages=2,
                            chunk_size=8, max_batch=1, preempt="priority")
    reqs = [Request("bg", 0.0, 48, 8, decode_len=3, priority=0),
            Request("hi", 0.3, 16, 8, decode_len=3, priority=1),
            Request("bg2", 0.4, 40, 8, decode_len=3, priority=0)]
    rep = eng.serve(reqs, verify=True, op_order="random",
                    rng=np.random.default_rng(3))  # verify: KV bit-exact
    assert sum(rep.preemptions.values()) > 0, "scenario produced no preemption"
    ex = eng.executor
    for r in reqs:
        out = ex.outputs(r.request_id)
        full = jnp.concatenate([ex.store.get(r.request_id).inputs,
                                ex.suffix_inputs(r.request_id)], axis=1)
        ref_logits, cache = m.prefill(params, full)
        np.testing.assert_allclose(np.asarray(out["first_logits"]),
                                   np.asarray(ref_logits), atol=1e-4)
        cache = grow_cache(cfg, cache, full.shape[1] + r.decode_len)
        logits, pos = ref_logits, full.shape[1]
        toks = [int(jnp.argmax(logits[0]))]
        for _ in range(r.decode_len - 1):
            inp = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits, cache = m.decode_step(params, inp, cache, pos)
            pos += 1
            toks.append(int(jnp.argmax(logits[0])))
        assert out["tokens"] == toks, r.request_id


# ---------------------------------------------------------------------------
# Trace schema v3: preempt/resume round-trip + replay; v2 still loads
# ---------------------------------------------------------------------------


def test_trace_v3_preemption_round_trip_and_replay():
    cost = _cost()
    cfg = cost.cfg
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, preempt="priority", strict=True)
    res, trace = capture(core, _burst(cfg))
    assert trace.version == TRACE_VERSION == 5
    assert trace.preempts() and trace.resumes()
    assert trace.meta["preempt"] == "priority"
    assert replay_trace(trace) == res            # bit-identical, incl. aborts
    loaded = ScheduleTrace.from_json(trace.to_json())
    assert loaded == trace
    assert replay_trace(loaded) == res
    assert loaded.captured_result().preemptions == res.preemptions


def test_trace_v2_loads_by_upgrade():
    """A pre-preemption (v2) trace — no priorities, no preempt meta, no
    preemptions in the result — loads cleanly and replays bit-identically
    under the implicit preempt="none" upgrade.  The capture uses
    priority-free requests: a real v2 engine had no SLO classes, so its
    schedule could not depend on them (since v5 the default I/O dispatch
    key IS priority-aware, so a priority-bearing capture would not survive
    having the field stripped)."""
    cost = _cost()
    cfg = cost.cfg
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, strict=True)
    reqs = _burst(cfg)
    for r in reqs:
        r.priority = 0
    res, trace = capture(core, reqs)
    d = trace.to_dict()
    d["version"] = 2
    del d["meta"]["preempt"]
    del d["result"]["preemptions"]
    for r in d["requests"]:
        r.pop("priority", None)
        r.pop("deadline", None)
    up = ScheduleTrace.from_dict(d)
    assert up.version == TRACE_VERSION
    assert replay_trace(up) == res


# ---------------------------------------------------------------------------
# Satellite: contention-aware marginal-benefit gate
# ---------------------------------------------------------------------------


def test_benefit_gate_prices_candidate_channel_slowdown():
    """A transfer that beats recompute at nominal bandwidth LOSES on a
    10x-degraded channel: the gate must flip, and the engine must recompute
    those units instead of loading them over the slow channel."""
    cost = _cost(bw="80Gbps")       # I/O clearly wins at nominal bandwidth
    cfg = cost.cfg
    backend = SimBackend(cost)
    plans = make_baseline_plans("cacheflow", "r", 16_000, chunk_size=512,
                                l_delta=0, num_layers=cfg.num_layers)
    unit = plans[0].plan.io_next
    assert backend.io_benefit(plans[0], unit, None, slowdown=1.0)
    assert not backend.io_benefit(plans[0], unit, None, slowdown=1000.0)

    def run(slowdown):
        core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                          channel_slowdown=slowdown, strict=True)
        return core.run([_req(cfg, "r", 16_000, new=0, dec=0)])

    fast, slow = run(None), run({0: 1000.0})
    loads = lambda r: sum(1 for *_, d in r.ops_log if ":l" in d)
    assert loads(fast) > 0            # nominal channel: gate admits transfers
    assert loads(slow) == 0           # degraded channel: recompute wins
    assert set(slow.restore_finish) == {"r"}


# ---------------------------------------------------------------------------
# Satellite: aborted transfers are not useful work
# ---------------------------------------------------------------------------


def test_aborted_transfer_excluded_from_io_busy_and_tagged():
    cost = _cost()
    cfg = cost.cfg
    kw = dict(stages=1, io_channels=2, strict=True)

    def mk():
        return [EngineRequest(rid, n, 0.0,
                              make_baseline_plans("lmcache", rid, n,
                                                  chunk_size=512, l_delta=0,
                                                  num_layers=cfg.num_layers))
                for rid, n in (("r0", 16_000), ("r1", 12_000))]

    dry = EngineCore(SimBackend(cost), **kw).run(mk())
    t0, t1 = next((t0, t1) for t0, t1, res, _ in dry.ops_log if res == "io1")
    res = EngineCore(SimBackend(cost), channel_fail_at={1: (t0 + t1) / 2},
                     **kw).run(mk())
    aborted = [(t0, t1) for t0, t1, rn, d in res.ops_log
               if d.endswith(":aborted")]
    assert aborted, "failure injected but no op tagged as aborted"
    useful = sum(t1 - t0 for t0, t1, rn, d in res.ops_log
                 if rn.startswith("io") and not d.endswith(":aborted"))
    wasted = sum(t1 - t0 for t0, t1 in aborted)
    assert res.io_busy == pytest.approx(useful / (2 * res.makespan))
    # the uncorrected (pre-fix) fraction would have counted the dead time
    assert res.io_busy < (useful + wasted) / (2 * res.makespan)


# ---------------------------------------------------------------------------
# Satellite: synthetic decode durations see the true batch composition
# ---------------------------------------------------------------------------


def test_real_decode_dur_fn_sees_full_batch():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    ex = RestorationExecutor(m, params, chunk_size=8, stages=1)
    seen = []

    def dur_fn(op):
        if op.kind == "decode":
            seen.append(op)
        return 0.5

    reqs = []
    # "a" decodes a long tail so "b" joins mid-decode: some steps MUST batch
    for rid, dec in (("a", 16), ("b", 4)):
        ex.remember(rid, jax.random.randint(RNG, (1, 24), 0, cfg.vocab_size))
        ex.set_suffix(rid, jax.random.randint(RNG, (1, 8), 0, cfg.vocab_size),
                      decode_len=dec)
        reqs.append(EngineRequest(rid, 24, 0.0,
                                  ex.make_plans(rid, l_delta=16),
                                  new_len=8, decode_len=dec))
    core = EngineCore(RealBackend(ex, dur_fn=dur_fn), stages=1,
                      io_channels=1, strict=True)
    core.run(reqs)
    assert seen, "no decode steps dispatched"
    # identical durations -> both requests decode in the same batched steps
    assert any(op.batch == ("a", "b") for op in seen)
    for op in seen:
        assert op.batch and op.request_id == op.batch[0]
        assert op.tokens == (0, len(op.batch))
