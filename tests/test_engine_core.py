"""The shared engine core: batched REAL restoration (N requests in flight,
randomized interleavings, per-request verification), backend-agnostic
scheduling parity, continuous-batching admission, KV-store tier integration
and failure injection — all through the one event loop both serving engines
use."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import (CostModel, EngineBackend, EngineCore, EngineRequest,
                        RealBackend, RestorationExecutor, SimBackend,
                        interleaving_dur_fn)
from repro.core.baselines import make_baseline_plans
from repro.models import build_model
from repro.serving import RealServingEngine, Request, TieredKVStore

RNG = jax.random.PRNGKey(0)
LENS = {"a": 40, "b": 24, "c": 32}


def _executor(arch="qwen3-8b", stages=1, chunk=8, lens=LENS):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    ex = RestorationExecutor(m, params, chunk_size=chunk, stages=stages)
    for rid, n in lens.items():
        if cfg.input_mode == "tokens":
            inputs = jax.random.randint(RNG, (1, n), 0, cfg.vocab_size)
        else:
            inputs = jax.random.normal(RNG, (1, n, cfg.d_model), jnp.float32)
        ex.remember(rid, inputs)
    return cfg, ex


def _engine_requests(cfg, ex, lens=LENS, system="cacheflow", l_delta=16):
    bounds = ex.bounds if ex.stages > 1 else None
    return [EngineRequest(rid, n, 0.0,
                          make_baseline_plans(system, rid, n,
                                              chunk_size=ex.chunk_size,
                                              l_delta=l_delta,
                                              num_layers=cfg.num_layers,
                                              stage_bounds=bounds))
            for rid, n in lens.items()]


# ---------------------------------------------------------------------------
# Tentpole acceptance: >= 3 requests restored CONCURRENTLY in real mode,
# every per-request cache verified against its full-prefill ground truth.
# ---------------------------------------------------------------------------


def test_batched_real_restoration_three_requests():
    cfg, ex = _executor()
    reqs = _engine_requests(cfg, ex)
    # seeded schedule durations: measured CPU timings occasionally let the
    # FIFO head run as a sequential block, making the interleaving
    # assertion below flaky; rng durations keep the schedule deterministic
    # while the ops still execute for real on device.  Two channels make
    # the interleaving structural: the surplus channel always prefetches a
    # non-head request (with one channel, FCFS compute + head-first I/O
    # legitimately drain requests as sequential blocks now that compute can
    # no longer double-claim the unit an in-flight transfer is restoring).
    dur = interleaving_dur_fn("random", np.random.default_rng(0))
    core = EngineCore(RealBackend(ex, dur_fn=dur), stages=1, io_channels=2,
                      strict=True)
    res = core.run(reqs)
    assert set(res.restore_finish) == set(LENS)
    for rid in LENS:
        ex.verify(rid)
    # the schedule truly interleaved: ops of different requests alternate
    # rather than running as three sequential blocks
    rids = [desc.split(":")[0] for _, _, _, desc in res.ops_log]
    switches = sum(1 for x, y in zip(rids, rids[1:]) if x != y)
    assert switches > len(LENS) - 1, rids


@pytest.mark.property
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_batched_real_any_interleaving_is_correct(seed):
    """Multi-request extension of the single-request interleaving property:
    rng-drawn op durations reorder completions (and hence every subsequent
    claim), and each restored cache must still match its ground truth."""
    cfg, ex = _executor(stages=2)
    reqs = _engine_requests(cfg, ex)
    dur = interleaving_dur_fn("random", np.random.default_rng(seed))
    core = EngineCore(RealBackend(ex, dur_fn=dur), stages=2, io_channels=2,
                      strict=True)
    core.run(reqs)
    for rid in LENS:
        ex.verify(rid)


def test_real_serving_engine_batched_with_admission():
    """RealServingEngine routes through the core: batched restoration under
    a continuous-batching cap, per-request verify + suffix prefill."""
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    eng = RealServingEngine(m, params, system="cacheflow", stages=2,
                            chunk_size=8, max_batch=2)
    reqs = [Request("a", 0.0, 40, 8), Request("b", 0.0, 24, 8),
            Request("c", 0.0, 32, 8)]
    rep = eng.serve(reqs, verify=True)     # verify raises on any KV mismatch
    assert set(rep.ttfts) == {"a", "b", "c"}
    assert all(v > 0 for v in rep.ttfts.values())


def test_real_failure_injection_recovers():
    """A transfer channel failing mid-restoration re-queues its claims; real
    re-execution is idempotent so every cache still verifies."""
    cfg, ex = _executor()
    reqs = _engine_requests(cfg, ex, system="lmcache")   # I/O-heavy
    dur = interleaving_dur_fn("alternate", np.random.default_rng(7))
    core = EngineCore(RealBackend(ex, dur_fn=dur), stages=1, io_channels=2,
                      channel_fail_at={1: 1.5}, strict=True)
    res = core.run(reqs)
    assert set(res.restore_finish) == set(LENS)
    for rid in LENS:
        ex.verify(rid)


# ---------------------------------------------------------------------------
# Backend-agnosticism: identical durations => identical scheduling decisions
# ---------------------------------------------------------------------------


class _ConstBackend(EngineBackend):
    def compute_secs(self, op, req):
        return 1.0

    def io_secs(self, op, req, bandwidth):
        return 1.0


def test_sim_and_real_backends_schedule_identically():
    cfg, ex = _executor()
    kw = dict(stages=1, io_channels=1, strict=True)
    res_real = EngineCore(RealBackend(ex, dur_fn=lambda op: 1.0),
                          **kw).run(_engine_requests(cfg, ex))
    cfg2, ex2 = _executor()
    res_stub = EngineCore(_ConstBackend(), **kw).run(_engine_requests(cfg2, ex2))
    assert [d for *_, d in res_real.ops_log] == [d for *_, d in res_stub.ops_log]
    assert res_real.restore_finish == res_stub.restore_finish


# ---------------------------------------------------------------------------
# Admission + KV-store integration (sim backend — pure event loop)
# ---------------------------------------------------------------------------


def _sim_core(**kw):
    cfg = get_config("qwen3-8b")
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    return cfg, EngineCore(SimBackend(cost), **kw)


def _sim_requests(cfg, lens, **plan_kw):
    return [EngineRequest(rid, n, 0.0,
                          make_baseline_plans("cacheflow", rid, n,
                                              chunk_size=256, l_delta=0,
                                              num_layers=cfg.num_layers,
                                              **plan_kw))
            for rid, n in lens.items()]


def test_admission_cap_serializes_requests():
    cfg, core = _sim_core(stages=1, io_channels=1, max_active=1)
    res = core.run(_sim_requests(cfg, {"r0": 8000, "r1": 8000}))
    assert res.restore_start["r1"] >= res.restore_finish["r0"]
    cfg, core2 = _sim_core(stages=1, io_channels=1, max_active=0)
    res2 = core2.run(_sim_requests(cfg, {"r0": 8000, "r1": 8000}))
    assert res2.restore_start["r1"] < res.restore_start["r1"]


def test_kvstore_touch_and_promote_on_restore():
    """Restoring a request must refresh its LRU position and pull the
    payload up a tier — previously dead TieredKVStore API, now wired into
    the engine loop."""
    store = TieredKVStore(hbm_cap=0, host_cap=10**9, remote_cap=10**12)
    cfg, core = _sim_core(stages=1, io_channels=1, kvstore=store)
    store.put("cold", 1000, tier="remote")
    store.put("hot", 1000, tier="remote")
    assert store.tier_of("cold") == "remote"
    res = core.run(_sim_requests(cfg, {"cold": 4000}))
    assert "cold" in res.restore_finish
    assert store.tier_of("cold") == "host"          # promoted on completion
    assert store.tier_of("hot") == "remote"         # untouched request stays
    # dispatch-time bandwidth: the loads saw the REMOTE tier's bandwidth,
    # so a full-chunk transfer takes exactly chunk_bytes / remote_bw
    # (orders of magnitude above what the host tier would give)
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"], mfu=0.45)
    io_durs = [t1 - t0 for t0, t1, res_name, _ in res.ops_log
               if res_name.startswith("io")]
    assert io_durs, "expected I/O dispatches"
    chunk_bytes = 256 * cost.bytes_per_token()
    assert max(io_durs) == pytest.approx(
        chunk_bytes / store.tiers["remote"].bandwidth, rel=1e-6)
    assert max(io_durs) > 10 * chunk_bytes / store.tiers["host"].bandwidth


def test_stalled_engine_raises_when_strict():
    cfg, core = _sim_core(stages=1, io_channels=1, strict=True,
                          channel_fail_at={0: 0.0})
    reqs = _sim_requests(cfg, {"r0": 4000})
    for r in reqs:                     # load-only plan, no working channel
        for p in r.plans:
            p.plan.comp_enabled = False
    with pytest.raises(RuntimeError, match="stalled"):
        core.run(reqs)
