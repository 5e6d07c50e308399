"""Serving layer: workloads, KV store tiers, simulation engine reproduces the
paper's qualitative results, real engine end-to-end."""
import jax
import numpy as np
import pytest

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (RealServingEngine, Request, SimServingEngine,
                           TieredKVStore, generate)
from repro.serving.metrics import cdf, percentiles


def test_workload_shapes():
    for w in ("lmsys_chat", "wildchat", "swe_bench"):
        reqs = generate(w, 50, seed=3)
        assert len(reqs) == 50
        lens = [r.prefix_len for r in reqs]
        assert max(lens) > 4000, w            # long-prefix mass (paper Fig 1a)
        arr = [r.arrival for r in reqs]
        assert arr == sorted(arr)
    # agentic prefix reuse
    sw = generate("swe_bench", 30, seed=0)
    assert len({r.prefix_id for r in sw}) < 30


def test_workload_determinism():
    a = generate("lmsys_chat", 20, seed=5)
    b = generate("lmsys_chat", 20, seed=5)
    assert [(r.prefix_len, r.arrival) for r in a] == \
           [(r.prefix_len, r.arrival) for r in b]


def test_kvstore_tiers_lru_spill():
    st = TieredKVStore(hbm_cap=100, host_cap=250, remote_cap=10_000,
                       hbm_bw=800e9, host_bw=100e9, remote_bw=1e9)
    st.put("a", 80, tier="hbm")
    st.put("b", 80, tier="hbm")            # spills "a" to host
    assert st.tier_of("b") == "hbm"
    assert st.tier_of("a") == "host"
    assert st.bandwidth_for("a") == 100e9
    st.put("c", 200, tier="host")          # spills "a" to remote
    assert st.tier_of("a") == "remote"
    st.promote("a", "host")
    assert st.tier_of("a") == "host"


def _run_sim(system, stages=2, **kw):
    cfg = get_config("qwen3-8b")
    reqs = generate("swe_bench", 24, seed=1)
    eng = SimServingEngine(cfg, HARDWARE["h100"],
                           io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                           system=system, stages=stages, max_batch=8, **kw)
    return eng.run(reqs)


def test_sim_reproduces_paper_ordering():
    """Paper §4.2: CacheFlow beats vLLM / LMCache / Cake on mean and tail."""
    reports = {s: _run_sim(s) for s in ("vllm", "lmcache", "cake", "cacheflow")}
    cf = reports["cacheflow"].stats
    for base in ("vllm", "lmcache", "cake"):
        bs = reports[base].stats
        assert cf["mean"] < bs["mean"], (base, cf["mean"], bs["mean"])
        assert cf["p90"] < bs["p90"] * 1.05, base
    # paper band: 1.1x-1.7x+ vs best baseline (we allow the upper side)
    best = min(reports[b].stats["mean"] for b in ("vllm", "lmcache", "cake"))
    assert best / cf["mean"] > 1.1


def test_sim_utilization_pattern():
    """Paper Fig. 5: vLLM compute-bound w/ idle IO; LMCache IO-bound w/ idle
    compute; CacheFlow high on both."""
    r_v = _run_sim("vllm")
    r_l = _run_sim("lmcache")
    r_c = _run_sim("cacheflow")
    assert r_v.io_busy < 0.05 and r_v.compute_busy > 0.3
    assert r_l.compute_busy < 0.05 and r_l.io_busy > 0.5
    assert r_c.compute_busy > r_l.compute_busy
    assert r_c.io_busy > r_v.io_busy


def test_sim_3d_ablation():
    """Paper Fig. 7: disabling stage-parallel restoration hurts."""
    r3d = _run_sim("cacheflow", stages=2)
    r2d = _run_sim("cacheflow_2d", stages=2)
    assert r3d.stats["mean"] < r2d.stats["mean"]


def test_sim_bandwidth_monotonicity():
    """Paper Fig. 8: more I/O bandwidth -> lower TTFT under CacheFlow."""
    cfg = get_config("qwen3-8b")
    means = []
    for bw in ("10Gbps", "40Gbps", "80Gbps"):
        reqs = generate("lmsys_chat", 16, seed=2)
        eng = SimServingEngine(cfg, HARDWARE["h100"],
                               io_bandwidth=IO_BANDWIDTHS[bw],
                               system="cacheflow", stages=1)
        means.append(eng.run(reqs).stats["mean"])
    assert means[0] >= means[1] >= means[2]


def test_real_engine_serves_and_verifies():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = RealServingEngine(m, params, system="cacheflow", stages=2, chunk_size=8)
    reqs = [Request("a", 0.0, 40, 8), Request("b", 0.0, 24, 8)]
    rep = eng.serve(reqs, verify=True)     # verify raises on any KV mismatch
    assert set(rep.ttfts) == {"a", "b"}
    assert all(v > 0 for v in rep.ttfts.values())


def test_metrics_helpers():
    vals = list(range(1, 101))
    st = percentiles(vals)
    assert st["p50"] == pytest.approx(50.5)
    pts = cdf(vals, n_points=11)
    assert pts[0][1] == 0.0 and pts[-1][1] == 1.0


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(monkeypatch, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the code then sets nothing;
    otherwise the cache is the checkout's git-ignored ``.jax_cache``."""
    import os
    from repro.launch import compile_cache as cc
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv(cc.ENV, "/elsewhere/cache")
            assert cc.use_compile_cache() == "/elsewhere/cache"
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv(cc.ENV, raising=False)
            path = cc.use_compile_cache()
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            with open(os.path.join(root, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
