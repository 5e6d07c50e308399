"""Continuous batching (DESIGN.md §11): streaming admission, decode overlap.

  * No starvation: under randomized arrivals/durations with a tight
    ``max_active`` cap, every queued request is admitted no later than the
    moment enough earlier work retired to free its slot (bounded wait —
    property test).
  * Slot accounting: a mid-flight retire frees a decode slot exactly once —
    the live-admission count never exceeds ``max_active`` and refills
    happen mid-flight (continuous) vs only at batch close (gang).
  * Trace schema v5: a captured continuous-batching run — prefetch
    dispatches, prefetch gates, decode-load-annotated benefit gates,
    admission meta — replays bit-identically in sim mode and in real mode
    with per-request cache verification.
  * Queued-request prefetch: idle channel time promotes a queued request's
    KV up a storage tier before admission.
  * Priority-aware I/O dispatch: an urgent request's transfers jump the
    channel queue; default SLO classes reproduce the classic ordering.
  * Decode-aware benefit gate: a transfer that loses to recompute on an
    idle device can win against a live decode batch.
"""
import numpy as np
import pytest

from _engine_helpers import RngBackend
from hypothesis import given, settings, strategies as st

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import (CostModel, EngineCore, EngineRequest, ScheduleTrace,
                        SimBackend, TraceRecorder, capture, replay_trace)
from repro.core.baselines import make_baseline_plans
from repro.core.plans import make_request_plans
from repro.core.scheduler import BatchScheduler
from repro.core.trace import TRACE_VERSION
from repro.serving import Request, SimServingEngine, TieredKVStore
from repro.serving.workloads import multi_tenant


def _cost(arch="qwen3-8b", hw="h100", bw="10Gbps", **kw):
    return CostModel(get_config(arch), HARDWARE[hw], IO_BANDWIDTHS[bw],
                     mfu=0.45, **kw)


def _rng_requests(rng, n, *, spacing=0.25):
    """Randomized lifecycle requests with strictly increasing arrivals (so
    FCFS rank is unambiguous)."""
    reqs = []
    t = 0.0
    for i in range(n):
        t += float(rng.uniform(0.01, spacing))
        tokens = int(rng.integers(16, 120))
        plans = make_request_plans(f"r{i}", tokens, chunk_size=8, l_delta=0,
                                   num_layers=4, stage_bounds=[(0, 4)],
                                   strategy="token")
        reqs.append(EngineRequest(f"r{i}", tokens, arrival=t, plans=plans,
                                  new_len=16, decode_len=int(rng.integers(1, 6))))
    return reqs


def _admission_timeline(trace):
    """(admits, finishes) as rid -> engine time from a captured trace."""
    admits, finishes = {}, {}
    for e in trace.events:
        if e.kind == "admit":
            admits[e.request_id] = e.t
        elif e.kind == "finish":
            finishes[e.request_id] = e.t
    return admits, finishes


# ---------------------------------------------------------------------------
# No starvation: bounded wait under randomized arrivals (property)
# ---------------------------------------------------------------------------


@pytest.mark.property
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_continuous_admission_bounded_wait(seed):
    """FCFS continuous admission never starves: with cap K, the i-th
    arrival (0-based, arrival order) is admitted no later than
    max(its arrival, the (i-K+1)-th finish overall) — the instant enough
    earlier work retired that a slot must have been free for it."""
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(1, 4))
    reqs = _rng_requests(rng, int(rng.integers(4, 9)))
    core = EngineCore(RngBackend(seed), stages=1,
                      io_channels=int(rng.integers(1, 3)),
                      max_active=cap, strict=True)
    res, trace = capture(core, reqs)
    admits, _ = _admission_timeline(trace)
    assert set(admits) == {r.request_id for r in reqs}   # no one starved
    assert set(res.finish) == set(admits)
    finish_order = sorted(res.finish.values())
    for i, r in enumerate(reqs):                         # arrival order
        bound = r.arrival if i < cap else \
            max(r.arrival, finish_order[i - cap])
        assert admits[r.request_id] <= bound + 1e-9, \
            (r.request_id, admits[r.request_id], bound)


# ---------------------------------------------------------------------------
# Slot accounting: mid-flight retire frees exactly one slot
# ---------------------------------------------------------------------------


def _slot_walk(trace, cap):
    """Replay admit/finish events; return (peak_active, admit_times_when_full)
    — admissions that happened while other requests were still live."""
    active, peak, midflight = set(), 0, []
    for e in trace.events:
        if e.kind == "admit":
            assert e.request_id not in active, "double admission"
            if active:
                midflight.append(e.t)
            active.add(e.request_id)
            peak = max(peak, len(active))
            assert len(active) <= cap
        elif e.kind == "finish":
            assert e.request_id in active, "finish freed a slot twice"
            active.remove(e.request_id)
    assert not active
    return peak, midflight


def test_midflight_retire_frees_slot_exactly_once():
    cost = _cost()
    cfg = cost.cfg

    def mk(i, arrival):
        n = 4_000 + 700 * i
        plans = make_baseline_plans("cacheflow", f"r{i}", n, chunk_size=512,
                                    l_delta=0, num_layers=cfg.num_layers)
        return EngineRequest(f"r{i}", n, arrival=arrival, plans=plans,
                             new_len=64, decode_len=8 + 4 * i)

    reqs = [mk(i, 0.1 * i) for i in range(6)]
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, strict=True)
    res, trace = capture(core, reqs)
    peak, midflight = _slot_walk(trace, cap=2)
    assert peak == 2
    # continuous batching: freed slots are refilled MID-FLIGHT — admissions
    # happen while another request is still live (restoring or decoding)
    assert midflight, "no mid-flight refill under continuous admission"
    assert set(res.finish) == {r.request_id for r in reqs}


def test_gang_admission_waits_for_batch_close():
    """The run-to-completion baseline: arrivals NEVER join a live batch —
    every admission happens either into an empty engine or at the instant
    the whole previous batch retired."""
    cost = _cost()
    cfg = cost.cfg

    def mk(i, arrival):
        n = 3_000 + 500 * i
        plans = make_baseline_plans("cacheflow", f"g{i}", n, chunk_size=512,
                                    l_delta=0, num_layers=cfg.num_layers)
        return EngineRequest(f"g{i}", n, arrival=arrival, plans=plans,
                             new_len=64, decode_len=8)

    reqs = [mk(i, 0.05 * i) for i in range(6)]
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, admission="gang", strict=True)
    res, trace = capture(core, reqs)
    active = set()
    batch_close_times = set()
    for e in trace.events:
        if e.kind == "admit":
            # gang: admission only into an empty engine or exactly at a
            # batch-close instant (same-timestamp group admissions allowed)
            assert not active or e.t in batch_close_times, \
                (e.request_id, e.t)
            active.add(e.request_id)
        elif e.kind == "finish":
            active.discard(e.request_id)
            if not active:
                batch_close_times.add(e.t)
    assert set(res.finish) == {r.request_id for r in reqs}
    # and the same stream under continuous admission strictly beats it on
    # mean TTFT: slots refill mid-flight instead of idling to batch close
    cont = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, strict=True).run(
        [mk(i, 0.05 * i) for i in range(6)])
    mean = lambda d, reqs: float(np.mean(  # noqa: E731
        [d[r.request_id] - r.arrival for r in reqs]))
    assert mean(cont.first_token, reqs) < mean(res.first_token, reqs)


def test_gang_rejects_preemption_and_unknown_admission():
    cost = _cost()
    with pytest.raises(ValueError, match="gang"):
        EngineCore(SimBackend(cost), admission="gang", preempt="priority")
    with pytest.raises(ValueError, match="admission"):
        EngineCore(SimBackend(cost), admission="bogus")


# ---------------------------------------------------------------------------
# Trace schema v5: sim + real replay with prefetch and decode-load gates
# ---------------------------------------------------------------------------


def _mt_requests(n=8, seed=11):
    # rate 8/s backlogs the 2-slot batch (so the idle channel prefetches a
    # queued request) and the 64-step decodes keep a live batch under every
    # restoration (so gates are priced with decode_load > 0)
    return [Request(r.request_id, r.arrival, min(r.prefix_len, 6_000),
                    min(r.new_len, 128), decode_len=min(r.decode_len, 64),
                    priority=r.priority, deadline=r.deadline)
            for r in multi_tenant(n, seed=seed, arrival_rate=8.0)]


def test_trace_v5_sim_replay_bit_identical_with_prefetch():
    """A continuous-batching capture — prefetch dispatches, prefetch gates,
    admission meta — replays bit-identically WITHOUT the KV store (every
    store-derived decision is pinned in the trace) and survives JSON."""
    cfg = get_config("qwen3-8b")
    store = TieredKVStore(remote_bw=IO_BANDWIDTHS["10Gbps"])
    eng = SimServingEngine(cfg, HARDWARE["h100"],
                           io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                           stages=2, max_batch=2, kvstore=store,
                           kv_tier="remote", prefetch=True,
                           decode_interference=0.3)
    rec = TraceRecorder()
    eng.run(_mt_requests(), trace=rec)
    trace = rec.trace
    assert trace.version == TRACE_VERSION == 5
    assert trace.meta["admission"] == "continuous"
    assert trace.meta["prefetch"] is True
    assert trace.prefetch_gates(), "no prefetch decisions captured"
    assert trace.prefetches(), "no prefetch transfers captured"
    assert any(e.decode_load for e in trace.gates()), \
        "no gate was priced against a live decode batch"
    res = trace.captured_result()
    assert res.overlap_decode_restore > 0.0
    assert replay_trace(trace) == res
    loaded = ScheduleTrace.from_json(trace.to_json())
    assert loaded == trace
    assert replay_trace(loaded) == res


def test_trace_v4_loads_by_upgrade():
    """A pre-continuous-batching (v4) trace — no admission/prefetch meta, no
    overlap in the result — loads cleanly and replays bit-identically under
    the implicit admission="continuous"/prefetch=False upgrade."""
    cost = _cost()
    cfg = cost.cfg
    plans = make_baseline_plans("cacheflow", "r", 6_000, chunk_size=512,
                                l_delta=0, num_layers=cfg.num_layers)
    core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                      max_active=2, strict=True)
    res, trace = capture(core, [EngineRequest("r", 6_000, 0.0, plans,
                                              new_len=64, decode_len=8)])
    d = trace.to_dict()
    d["version"] = 4
    del d["meta"]["admission"], d["meta"]["prefetch"]
    del d["result"]["overlap_decode_restore"]
    up = ScheduleTrace.from_dict(d)
    assert up.version == TRACE_VERSION
    rep = replay_trace(up)
    assert rep == res          # incl. the overlap recomputed from ops_log


def test_trace_v5_real_replay_with_cache_verification():
    """Real mode: a continuous-batching lifecycle capture re-executes on
    device with per-request cache verification under the recorded
    interleaving."""
    from repro.core.executor import RestorationExecutor
    from repro.models import build_model
    import jax

    from repro.serving import RealServingEngine

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = RealServingEngine(m, params, system="cacheflow", stages=2,
                            chunk_size=8, max_batch=2)
    reqs = [Request("a", 0.0, 40, 8, decode_len=4),
            Request("b", 0.05, 24, 8, decode_len=3),
            Request("c", 0.1, 32, 8, decode_len=4)]
    rec = TraceRecorder()
    res = eng.serve(reqs, op_order="random",
                    rng=np.random.default_rng(7), trace=rec)
    trace = rec.trace
    assert trace.version == TRACE_VERSION
    assert trace.meta["admission"] == "continuous"
    # sim replay of the real capture is bit-identical
    assert replay_trace(trace) == trace.captured_result()
    # real replay: every dispatched op re-executes on device; each restored
    # cache is verified against full-prefill ground truth
    ex = RestorationExecutor(m, params, chunk_size=8, stages=2)
    rng = jax.random.PRNGKey(9)
    for r in reqs:
        rng, key = jax.random.split(rng)
        if cfg.input_mode == "tokens":
            inputs = jax.random.randint(key, (1, r.prefix_len), 0,
                                        cfg.vocab_size)
        else:
            inputs = jax.random.normal(key, (1, r.prefix_len, cfg.d_model))
        ex.remember(r.request_id, inputs)
        rng, key = jax.random.split(rng)
        if cfg.input_mode == "tokens":
            suffix = jax.random.randint(key, (1, r.new_len), 0, cfg.vocab_size)
        else:
            suffix = jax.random.normal(key, (1, r.new_len, cfg.d_model))
        ex.set_suffix(r.request_id, suffix, decode_len=r.decode_len)
    rep = replay_trace(trace, ex, verify=True)
    assert rep == trace.captured_result()
    assert set(rep.finish) == set(res.finishes)


# ---------------------------------------------------------------------------
# Queued-request prefetch (satellite)
# ---------------------------------------------------------------------------


def test_prefetch_promotes_queued_requests():
    """With a hard admission cap, queued requests' KV is promoted remote ->
    host on idle channel time; their admission-time restoration then rides
    the faster tier.  Disabled, the trace carries no prefetch events."""
    cfg = get_config("qwen3-8b")

    def serve(prefetch):
        store = TieredKVStore(remote_bw=IO_BANDWIDTHS["10Gbps"])
        eng = SimServingEngine(cfg, HARDWARE["h100"],
                               io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                               stages=1, max_batch=1, kvstore=store,
                               kv_tier="remote", prefetch=prefetch)
        # q0 holds the single slot through a long decode — the channel
        # idles meanwhile, which is exactly the prefetch window for the
        # queued q1/q2 (small enough to finish promoting before admission)
        reqs = [Request("q0", 0.0, 4_000, 64, decode_len=120),
                Request("q1", 0.0, 1_500, 64, decode_len=8),
                Request("q2", 0.0, 2_000, 64, decode_len=8)]
        rec = TraceRecorder()
        rep = eng.run(reqs, trace=rec)
        return rep, rec.trace, store

    rep_on, trace_on, store_on = serve(True)
    rep_off, trace_off, _ = serve(False)
    assert not trace_off.prefetches()
    pf_rids = {e.op["request_id"] for e in trace_on.prefetches()}
    assert pf_rids, "no queued request was prefetched"
    # only QUEUED requests are prefetched (q0 is admitted immediately)
    assert "q0" not in pf_rids
    # the prefetched requests' restoration was strictly faster: their
    # transfers rode host bandwidth instead of the remote link
    for rid in pf_rids:
        assert rep_on.restore_secs[rid] < rep_off.restore_secs[rid]
    # prefetch decisions are pinned: the capture replays without the store
    assert replay_trace(trace_on) == trace_on.captured_result()


def test_prefetch_aborted_when_admission_wins_race():
    """A short-lived batch admits the queued request while its prefetch is
    still inflight: the transfer is cancelled (channel freed for the
    foreground restoration), so prefetch is never WORSE than off — and the
    abort is derived state, replaying bit-identically without the store."""
    cfg = get_config("qwen3-8b")

    def serve(prefetch):
        store = TieredKVStore(remote_bw=IO_BANDWIDTHS["10Gbps"])
        eng = SimServingEngine(cfg, HARDWARE["h100"],
                               io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                               stages=1, max_batch=1, kvstore=store,
                               kv_tier="remote", prefetch=prefetch)
        reqs = [Request("q0", 0.0, 4_000, 64, decode_len=8),
                Request("q1", 0.0, 1_500, 64, decode_len=8),
                Request("q2", 0.0, 2_000, 64, decode_len=8)]
        rec = TraceRecorder()
        return eng.run(reqs, trace=rec), rec.trace

    rep_on, trace_on = serve(True)
    rep_off, _ = serve(False)
    aborted = [e for e in trace_on.events
               if e.kind == "abort" and e.op
               and e.op.get("kind") == "prefetch"]
    assert aborted, "q0's 8-step decode should outpace the prefetches"
    for rid in ("q1", "q2"):   # cancelled background work costs nothing
        assert rep_on.restore_secs[rid] == \
            pytest.approx(rep_off.restore_secs[rid])
    assert replay_trace(trace_on) == trace_on.captured_result()


# ---------------------------------------------------------------------------
# Priority/deadline-aware I/O dispatch (satellite)
# ---------------------------------------------------------------------------


def _two_plans(sched, *, prio=None, deadline=None):
    cfg = get_config("qwen3-8b")
    for i, rid in enumerate(("first", "urgent")):
        plans = make_baseline_plans("lmcache", rid, 8_000 - 2_000 * i,
                                    chunk_size=512, l_delta=0,
                                    num_layers=cfg.num_layers)
        kw = {}
        if prio is not None:
            kw["priority"] = prio[i]
        if deadline is not None:
            kw["deadline"] = deadline[i]
        sched.add_request(plans, **kw)


def test_priority_jumps_io_queue():
    """Same candidates, three SLO configurations: default classes keep the
    classic longest-remaining-first order; a higher priority (or tighter
    deadline) makes the urgent request's transfer dispatch first."""
    s = BatchScheduler()
    _two_plans(s)
    assert s.next_io().request_id == "first"     # classic: FCFS head leads

    s = BatchScheduler()
    _two_plans(s, prio=(0, 2))
    assert s.next_io().request_id == "urgent"    # priority jumps the queue

    s = BatchScheduler()
    _two_plans(s, deadline=(120.0, 1.5))
    assert s.next_io().request_id == "urgent"    # deadline breaks the tie


# ---------------------------------------------------------------------------
# Decode-aware marginal-benefit gate (satellite)
# ---------------------------------------------------------------------------


def test_benefit_gate_flips_under_live_decode_batch():
    """A transfer that loses to recompute on an IDLE device wins once the
    recompute alternative is priced against a live decode batch eating
    ``decode_interference`` of the chips; with interference 0 the live
    batch changes nothing (bit-compat default).  The tight case is the
    LAST restoration chunk (pointers converged, one unit left): early
    gates price recompute over the whole remaining span and always pass."""
    idle = SimBackend(_cost())
    busy = SimBackend(_cost(decode_interference=0.6))
    flipped = False
    for n in range(8_192, 33_000, 4_096):
        p = make_baseline_plans("cacheflow", "r", n, chunk_size=512,
                                l_delta=0,
                                num_layers=idle.cost.cfg.num_layers)[0]
        p.plan.comp_next = p.plan.io_next     # one chunk left to cover
        unit = p.plan.io_next
        base = idle.io_benefit(p, unit, None)
        # interference without a live batch changes nothing
        assert busy.io_benefit(p, unit, None) == base
        assert idle.io_benefit(p, unit, None, decode_load=8) == base
        if not base and busy.io_benefit(p, unit, None, decode_load=8):
            flipped = True
    assert flipped, "no length where a live decode batch flips the gate"
