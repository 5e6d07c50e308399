"""Full request-lifecycle engine core: RESTORING -> PREFILL -> DECODE -> DONE.

  * TTFT contention: suffix prefill is a *scheduled* op — under load it
    queues behind other requests' restoration chunks, so TTFT exceeds the
    old bolt-on (restore + isolated prefill) estimate.
  * Phase monotonicity: restore_start <= restore_end <= first_token <=
    finish under randomized interleavings (property test).
  * Real-mode parity (tentpole acceptance): >= 3 concurrent requests with
    decode_len > 0 produce first-token logits and greedy decode outputs
    that match a no-restoration full-prefill+decode reference.
  * Lifecycle traces: capture covers prefill + decode_step events and
    replays bit-identically; v1 (pre-lifecycle) traces load by upgrade and
    unknown versions are rejected (no KeyError).
  * Admission: continuous-batching slots are freed at DECODE completion,
    not restore completion.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from _engine_helpers import RngBackend

from repro.core import (CostModel, EngineCore, EngineRequest,
                        RestorationExecutor, ScheduleTrace, SimBackend,
                        TraceRecorder, TraceVersionError, capture,
                        replay_trace)
from repro.core.baselines import make_baseline_plans
from repro.core.plans import make_request_plans
from repro.core.trace import TRACE_VERSION
from repro.models import build_model
from repro.models.kvcache import grow_cache
from repro.serving import RealServingEngine, Request

RNG = jax.random.PRNGKey(0)


def _cost(arch="qwen3-8b", hw="h100", bw="10Gbps"):
    return CostModel(get_config(arch), HARDWARE[hw], IO_BANDWIDTHS[bw], mfu=0.45)


# ---------------------------------------------------------------------------
# TTFT under load: contended prefill > bolt-on estimate
# ---------------------------------------------------------------------------


def test_ttft_under_load_exceeds_bolt_on_estimate():
    """r0 grinds a long compute-only restoration; r1 restores quickly over
    I/O but its suffix prefill must then queue FCFS behind r0's chunks —
    the old post-loop bolt-on (restore_finish + isolated prefill) strictly
    underestimates its TTFT."""
    cost = _cost()
    cfg = cost.cfg
    r0_plans = make_baseline_plans("vllm", "r0", 30_000, chunk_size=512,
                                   l_delta=0, num_layers=cfg.num_layers)
    r1_plans = make_baseline_plans("lmcache", "r1", 4_000, chunk_size=512,
                                   l_delta=0, num_layers=cfg.num_layers)
    reqs = [EngineRequest("r0", 30_000, 0.0, r0_plans),
            EngineRequest("r1", 4_000, 0.0, r1_plans, new_len=256)]
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1, strict=True)
    res = core.run(reqs)
    bolt_on = res.restore_finish["r1"] + cost.t_comp_range(4_000, 4_256, chunks=1)
    # the prefill waited for r0's restoration to drain off the stage compute
    assert res.first_token["r1"] > bolt_on * 1.5
    assert res.first_token["r1"] >= res.restore_finish["r0"]
    # and the op actually ran as a scheduled unit on the stage resource
    assert any(desc == "r1:p0" for *_, desc in res.ops_log)


def test_restoration_only_requests_collapse_to_old_behavior():
    cost = _cost()
    plans = make_baseline_plans("cacheflow", "r", 8_000, chunk_size=512,
                                l_delta=0, num_layers=cost.cfg.num_layers)
    res = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                     strict=True).run([EngineRequest("r", 8_000, 0.0, plans)])
    assert res.finish == res.restore_finish      # lifecycle collapsed
    assert res.first_token == {}                 # no token was produced
    assert res.decode_steps == 0


def test_admission_slot_held_through_decode():
    """Continuous batching frees capacity at DECODE completion: with
    max_active=1, r1 cannot even start restoring until r0 finishes
    decoding — previously the slot freed at restore completion."""
    cost = _cost()

    def mk(rid):
        plans = make_baseline_plans("cacheflow", rid, 6_000, chunk_size=512,
                                    l_delta=0, num_layers=cost.cfg.num_layers)
        return EngineRequest(rid, 6_000, 0.0, plans, new_len=128, decode_len=16)

    res = EngineCore(SimBackend(cost), stages=1, io_channels=1, max_active=1,
                     strict=True).run([mk("r0"), mk("r1")])
    assert res.finish["r0"] > res.restore_finish["r0"]      # decode tail exists
    assert res.restore_start["r1"] >= res.finish["r0"]


# ---------------------------------------------------------------------------
# Phase monotonicity under randomized interleavings (property)
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_phase_transitions_monotone(seed):
    rng = np.random.default_rng(seed)
    stages = int(rng.integers(1, 3))
    bounds = [(0, 2), (2, 4)][:stages]
    if stages == 1:
        bounds = [(0, 4)]
    reqs = []
    for i in range(int(rng.integers(3, 7))):
        n = int(rng.integers(16, 120))
        plans = make_request_plans(f"r{i}", n, chunk_size=8,
                                   l_delta=0, num_layers=4,
                                   stage_bounds=bounds, strategy="token")
        reqs.append(EngineRequest(
            f"r{i}", n, arrival=float(rng.uniform(0, 2.0)), plans=plans,
            new_len=int(rng.integers(0, 3)) * 16,
            decode_len=int(rng.integers(0, 6))))
    core = EngineCore(RngBackend(seed), stages=stages,
                      io_channels=int(rng.integers(1, 3)),
                      max_active=int(rng.integers(0, 4)), strict=True)
    res = core.run(reqs)
    for r in reqs:
        rid = r.request_id
        assert rid in res.restore_finish and rid in res.finish
        assert res.restore_start[rid] <= res.restore_finish[rid]
        if r.new_len > 0 or r.decode_len > 0:
            assert rid in res.first_token
            assert res.restore_finish[rid] <= res.first_token[rid]
            assert res.first_token[rid] <= res.finish[rid]
            if r.decode_len > 1:
                assert res.finish[rid] > res.first_token[rid]
        else:
            assert rid not in res.first_token
            assert res.finish[rid] == res.restore_finish[rid]


# ---------------------------------------------------------------------------
# Real-mode lifecycle parity (tentpole acceptance)
# ---------------------------------------------------------------------------


def _real_engine():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    eng = RealServingEngine(m, params, system="cacheflow", stages=2,
                            chunk_size=8, max_batch=2)
    reqs = [Request("a", 0.0, 40, 8, decode_len=4),
            Request("b", 0.0, 24, 8, decode_len=3),
            Request("c", 0.0, 32, 8, decode_len=4)]
    return cfg, m, params, eng, reqs


def test_real_lifecycle_parity_vs_full_prefill_reference():
    """>= 3 concurrent requests through the engine core: per-request
    first-token logits and greedy decode outputs must match a
    no-restoration full-prefill + decode reference."""
    cfg, m, params, eng, reqs = _real_engine()
    rep = eng.serve(reqs, verify=True)        # verify raises on KV mismatch
    assert set(rep.ttfts) == {"a", "b", "c"}
    assert all(v > 0 for v in rep.ttfts.values())
    assert all(rep.e2e[rid] >= rep.ttfts[rid] for rid in rep.ttfts)
    ex = eng.executor
    for r in reqs:
        out = ex.outputs(r.request_id)
        full = jnp.concatenate([ex.store.get(r.request_id).inputs,
                                ex.suffix_inputs(r.request_id)], axis=1)
        ref_logits, cache = m.prefill(params, full)
        np.testing.assert_allclose(np.asarray(out["first_logits"]),
                                   np.asarray(ref_logits), atol=1e-4)
        # greedy decode reference on the un-restored cache
        cache = grow_cache(cfg, cache, full.shape[1] + r.decode_len)
        logits, pos = ref_logits, full.shape[1]
        toks = [int(jnp.argmax(logits[0]))]
        for _ in range(r.decode_len - 1):
            inp = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits, cache = m.decode_step(params, inp, cache, pos)
            pos += 1
            toks.append(int(jnp.argmax(logits[0])))
        assert out["tokens"] == toks, r.request_id
        assert len(out["step_logits"]) == r.decode_len - 1


def test_real_lifecycle_capture_replays_bit_identical():
    """A captured lifecycle schedule (incl. prefill + decode_step events)
    replays bit-identically through the sim side and survives JSON."""
    *_, eng, reqs = _real_engine()
    rec = TraceRecorder()
    res = eng.serve(reqs, op_order="random",
                    rng=np.random.default_rng(5), trace=rec)
    trace = rec.trace
    assert trace.prefills(), "no prefill events captured"
    assert trace.decode_steps(), "no decode_step events captured"
    rep = replay_trace(trace)
    assert rep == trace.captured_result()
    loaded = ScheduleTrace.from_json(trace.to_json())
    assert loaded == trace
    assert replay_trace(loaded) == trace.captured_result()
    assert set(res.ttfts) == set(rep.first_token)


def test_sim_lifecycle_capture_replays_bit_identical():
    """Sim capture of the same workload shape: the whole-lifecycle schedule
    (prefill ops contending with restoration, batched decode steps) is a
    replayable artifact."""
    cfg = get_config("qwen3-8b").reduced()
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    bounds = [(0, cfg.num_layers // 2), (cfg.num_layers // 2, cfg.num_layers)]
    reqs = [EngineRequest(rid, n, 0.0,
                          make_baseline_plans("cacheflow", rid, n,
                                              chunk_size=8, l_delta=16,
                                              num_layers=cfg.num_layers,
                                              stage_bounds=bounds),
                          new_len=8, decode_len=d)
            for rid, n, d in (("a", 40, 4), ("b", 24, 3), ("c", 32, 4))]
    core = EngineCore(SimBackend(cost, benefit_gate=False), stages=2,
                      io_channels=2, strict=True)
    res, trace = capture(core, reqs)
    assert len(trace.prefills()) == 2 * 3          # one per stage per request
    assert trace.decode_steps()
    assert set(res.first_token) == {"a", "b", "c"}
    rep = replay_trace(trace)
    assert rep == res
    assert rep.ops_log == res.ops_log
    assert replay_trace(ScheduleTrace.from_json(trace.to_json())) == res


# ---------------------------------------------------------------------------
# Trace schema versioning (satellite)
# ---------------------------------------------------------------------------


def _restoration_only_trace():
    cost = _cost()
    plans = make_baseline_plans("cacheflow", "r", 4_000, chunk_size=512,
                                l_delta=0, num_layers=cost.cfg.num_layers)
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1, strict=True)
    return capture(core, [EngineRequest("r", 4_000, 0.0, plans)])


def test_trace_v1_loads_by_upgrade():
    """A pre-lifecycle (v1) trace — no new_len/decode_len, no lifecycle
    result fields — loads cleanly and replays to the captured result."""
    res, trace = _restoration_only_trace()
    d = trace.to_dict()
    d["version"] = 1
    for r in d["requests"]:
        del r["new_len"], r["decode_len"]
    for f in ("first_token", "finish", "decode_busy", "decode_steps"):
        del d["result"][f]
    up = ScheduleTrace.from_dict(d)
    assert up.version == TRACE_VERSION
    assert replay_trace(up) == res               # incl. upgraded result fields


def test_trace_version_gate_rejects_unknown_and_missing():
    _, trace = _restoration_only_trace()
    d = trace.to_dict()
    d["version"] = 99
    with pytest.raises(TraceVersionError, match="unsupported"):
        ScheduleTrace.from_dict(d)
    del d["version"]
    with pytest.raises(TraceVersionError, match="no schema version"):
        ScheduleTrace.from_dict(d)
