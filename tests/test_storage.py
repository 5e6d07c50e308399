"""Materialized chunk-granular KV storage subsystem (DESIGN.md §10).

Covers: the shared placement core's demotion cascade (regression for the
historical ``TieredKVStore._evict_for`` over-fill/silent-drop), dedup
refcount + bytes-conservation invariants under randomized op sequences,
quantize/dequantize round trips through the tiers, real-mode restoration
served from actual stored chunk bytes (bit-matching the full-prefill
reference un-quantized, within the documented tolerance with int8),
residency-based transfer skipping for dedup hits, and eviction-mode
preemption (drop + restart from the store)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.trace import TraceRecorder, replay_trace
from repro.models import build_model
from repro.serving import (ChunkStore, RealServingEngine, Request,
                           SimServingEngine, TieredKVStore)
from repro.storage import PlacementCore, Tier, chunk_hash_chain
from repro.config import HARDWARE, IO_BANDWIDTHS

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Placement core: cascading demotion (satellite regression)
# ---------------------------------------------------------------------------


def test_evict_cascade_when_tier_below_full():
    """Demoting out of a full tier into another full tier must cascade,
    never over-fill: the historical _evict_for stopped at one level."""
    st_ = TieredKVStore(hbm_cap=100, host_cap=100, remote_cap=100,
                        hbm_bw=800e9, host_bw=100e9, remote_bw=1e9)
    st_.put("a", 90, tier="hbm")
    st_.put("b", 90, tier="host")
    st_.put("c", 90, tier="remote")
    st_.put("d", 90, tier="hbm")     # a->host forces b->remote forces c off
    assert st_.tier_of("d") == "hbm"
    assert st_.tier_of("a") == "host"
    assert st_.tier_of("b") == "remote"
    assert st_.tier_of("c") is None            # dropped, counted — not silent
    assert st_.core.drops == 1
    for t in st_.tiers.values():
        assert t.used <= t.capacity
    st_.core.audit()


def test_oversized_entry_skips_tier_instead_of_overfilling():
    """An entry larger than a tier's whole capacity must not evict that
    tier to zero and then over-fill it; it belongs in the first tier that
    can hold it."""
    st_ = TieredKVStore(hbm_cap=100, host_cap=250, remote_cap=10_000)
    st_.put("small", 80, tier="hbm")
    st_.put("big", 300, tier="hbm")    # > hbm and > host capacity
    assert st_.tier_of("big") == "remote"
    assert st_.tier_of("small") == "hbm"       # untouched: no pointless evict
    for t in st_.tiers.values():
        assert t.used <= t.capacity
    st_.core.audit()


def test_placement_benefit_aware_eviction():
    """victim_fn orders eviction by benefit, not recency."""
    benefit = {"cheap": 1.0, "precious": 100.0, "newer": 50.0}
    core = PlacementCore([Tier("hot", 1e9, 200), Tier("cold", 1e6, 1000)],
                         victim_fn=lambda k: benefit[k])
    core.put("precious", "hot", nbytes=90)
    core.put("cheap", "hot", nbytes=90)
    core.put("newer", "hot", nbytes=90)        # someone must go
    # LRU would evict "precious" (oldest); benefit-aware evicts "cheap"
    assert core.tier_of("cheap") == "cold"
    assert core.tier_of("precious") == "hot"
    assert core.tier_of("newer") == "hot"
    core.audit()


@pytest.mark.property
@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_ops=st.integers(5, 60))
def test_placement_randomized_invariants(seed, n_ops):
    """Under random put/touch/promote/remove: per-tier byte accounting is
    conserved, no tier over capacity, placement map consistent."""
    rng = np.random.default_rng(seed)
    core = PlacementCore([Tier("a", 1e9, 500), Tier("b", 1e8, 800),
                          Tier("c", 1e6, 1200)])
    keys = [f"k{i}" for i in range(12)]
    for _ in range(n_ops):
        k = keys[rng.integers(len(keys))]
        op = rng.integers(4)
        if op == 0:
            core.put(k, ["a", "b", "c"][rng.integers(3)],
                     nbytes=int(rng.integers(10, 400)))
        elif op == 1:
            core.touch(k)
        elif op == 2:
            core.promote(k, ["a", "b"][rng.integers(2)])
        else:
            core.remove(k)
        core.audit()


# ---------------------------------------------------------------------------
# Chunk store: hashing, dedup, refcounts, quantized round trips
# ---------------------------------------------------------------------------


def _toy_cache(n_layers=2, n_tok=16, heads=2, dh=8, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {
        "k": jax.random.normal(ks[0], (n_layers, 1, n_tok, heads, dh), dtype),
        "v": jax.random.normal(ks[1], (n_layers, 1, n_tok, heads, dh), dtype),
        "kpos": jnp.tile(jnp.arange(n_tok, dtype=jnp.int32), (n_layers, 1)),
    }


def test_chunk_hash_chain_prefix_dependence():
    a = np.arange(16)[None]
    b = a.copy(); b[0, 0] = 99                  # differs in the FIRST chunk
    ka, kb = chunk_hash_chain(a, 4), chunk_hash_chain(b, 4)
    assert ka[0] != kb[0]
    # prefix chaining: EVERY later chunk key differs too (same tokens,
    # different prefix)
    assert all(x != y for x, y in zip(ka, kb))
    # identical prefixes share keys
    c = a.copy(); c[0, 15] = 99                 # differs only in the LAST chunk
    kc = chunk_hash_chain(c, 4)
    assert kc[:3] == ka[:3] and kc[3] != ka[3]


def test_chunkstore_dedup_single_copy_with_refcounts():
    cs = ChunkStore(chunk_size=4)
    cache = _toy_cache()
    cs.put_request("a", np.arange(16)[None], cache)
    bytes_once = cs.bytes_put
    cs.put_request("b", np.arange(16)[None], cache)
    assert cs.bytes_put == bytes_once           # one stored copy
    assert cs.dedup_hits == 4
    assert all(cs.chunks[k].refcount == 2 for k in cs.requests["a"])
    cs.free_request("a")
    assert all(cs.chunks[k].refcount == 1 for k in cs.requests["b"])
    cs.audit()


@pytest.mark.property
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_ops=st.integers(4, 30))
def test_chunkstore_randomized_put_evict_free_invariants(seed, n_ops):
    """Refcounts never go negative and tier byte accounting is conserved
    under randomized put/free/promote/touch sequences with tight tiers
    (forcing demotion cascades and bottom-tier drops)."""
    rng = np.random.default_rng(seed)
    cs = ChunkStore(chunk_size=4, hbm_cap=4096, host_cap=8192, disk_cap=16384,
                    quant="int8" if seed % 2 else "none")
    caches = {n: _toy_cache(seed=n) for n in range(3)}
    live = set()
    for i in range(n_ops):
        op = rng.integers(4)
        rid = f"r{rng.integers(6)}"
        if op == 0:
            n = int(rng.integers(3))
            cs.put_request(rid, (np.arange(16) + n)[None], caches[n],
                           tier=["hbm", "host", "disk"][rng.integers(3)])
            live.add(rid)
        elif op == 1 and rid in live:
            cs.free_request(rid)
            live.discard(rid)
        elif op == 2:
            cs.touch(rid)
        elif op == 3 and rid in live:
            for key in cs.requests[rid]:
                cs.fetch(key)
        cs.audit()
        assert all(c.refcount >= 0 for c in cs.chunks.values())


@pytest.mark.property
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_chunk_demote_quantize_promote_dequantize_round_trip(seed):
    """put -> demote to disk (quantize) -> promote/fetch (dequantize)
    stays within the store's documented int8 tolerance."""
    cs = ChunkStore(chunk_size=8, quant="int8")
    cache = _toy_cache(seed=seed)
    cs.put_request("r", np.arange(16)[None], cache, tier="disk")
    pays = cs.fetch_range("r", 0, 16)
    assert pays is not None and cs.bytes_transferred > 0
    tol = cs.quant_tolerance()
    assert 0 < tol < 0.5
    for c0, c1, pay in pays:
        for f in ("k", "v"):
            ref = np.asarray(cache[f][:, :, c0:c1], np.float32)
            got = np.asarray(pay[f], np.float32)
            assert np.max(np.abs(ref - got)) <= tol
        np.testing.assert_array_equal(np.asarray(pay["kpos"]),
                                      np.asarray(cache["kpos"][:, c0:c1]))
    cs.audit()


def test_int8_store_put_to_hbm_stays_exact_until_demotion():
    """Quantization applies on DEMOTION below HBM, never at put: a chunk
    placed straight into the hbm tier under quant="int8" serves bit-exact
    bytes; only once capacity pressure demotes it does the int8 form
    become authoritative."""
    cs = ChunkStore(chunk_size=8, quant="int8", hbm_cap=1 << 20)
    cache = _toy_cache()
    cs.put_request("r", np.arange(16)[None], cache, tier="hbm")
    pays = cs.fetch_range("r", 0, 16)
    assert cs.bytes_transferred == 0            # resident: nothing moved
    for c0, c1, pay in pays:
        for f in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pay[f]), np.asarray(cache[f][:, :, c0:c1]))
    # force a demotion: now (and only now) the stored form is lossy
    for key in cs.requests["r"]:
        cs.core.put(key, "host")
    got = cs.fetch_range("r", 0, 8)[0][2]["k"]
    ref = np.asarray(cache["k"][:, :, 0:8], np.float32)
    err = np.max(np.abs(ref - np.asarray(got, np.float32)))
    assert 0 < err <= cs.quant_tolerance()
    cs.audit()


def test_chunkstore_unquantized_round_trip_bit_exact_through_disk(tmp_path):
    """quant="none" must round-trip every tier (including real .npz files
    under --store-dir) bit-exactly, bf16 included."""
    cs = ChunkStore(chunk_size=8, quant="none", store_dir=str(tmp_path))
    cache = _toy_cache()
    cs.put_request("r", np.arange(16)[None], cache, tier="disk")
    assert any(f.endswith(".npz") for f in os.listdir(tmp_path))
    pays = cs.fetch_range("r", 0, 16)
    for c0, c1, pay in pays:
        for f in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pay[f]), np.asarray(cache[f][:, :, c0:c1]))
    cs.audit()


def test_chunkstore_benefit_eviction_prefers_low_benefit_chunks():
    """Under HBM pressure the evicted chunk is the one with the least
    recompute benefit per byte: early-prefix chunks (cheap to recompute)
    demote before late ones, refcount-0 chunks before referenced ones."""
    cache = _toy_cache(n_tok=16)
    raw_chunk = sum(np.asarray(cache[f][:, :, :4]).nbytes for f in ("k", "v"))
    raw_chunk += np.asarray(cache["kpos"][:, :4]).nbytes
    cs = ChunkStore(chunk_size=4, hbm_cap=raw_chunk * 3 + 1, host_cap=1 << 20)
    cs.put_request("r", np.arange(16)[None], cache, tier="hbm")  # 4 chunks, 3 fit
    keys = cs.requests["r"]
    tiers = [cs.core.tier_of(k) for k in keys]
    assert tiers.count("hbm") == 3
    # the demoted chunk is the EARLIEST (lowest t1^2 - t0^2 recompute saving)
    assert cs.core.tier_of(keys[0]) == "host"
    assert all(t == "hbm" for t in tiers[1:])


# ---------------------------------------------------------------------------
# Real-mode restoration served from the materialized store
# ---------------------------------------------------------------------------


def _real_engine(store, **kw):
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    return RealServingEngine(m, params, system=kw.pop("system", "cacheflow"),
                             stages=kw.pop("stages", 2), chunk_size=8,
                             kvstore=store, **kw)


def test_real_restore_from_store_bit_matches_reference():
    """Load-only restoration (every byte comes out of the store's tiers)
    must reproduce the full-prefill reference cache BIT-exactly when
    un-quantized; the executor's verify() (strict kpos + tight atol)
    passes and the store actually moved bytes."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store, system="lmcache")       # load-only baseline
    reqs = [Request("r0", 0.0, 32, 0, decode_len=0)]
    eng.serve(reqs, verify=True)
    ex = eng.executor
    live = ex.live_cache("r0")
    ref = ex.store.get("r0").kv_reference
    for f in ref:
        np.testing.assert_array_equal(np.asarray(live[f]), np.asarray(ref[f]),
                                      err_msg=f)
    assert store.fetches > 0 and store.bytes_transferred > 0


def test_real_restore_int8_within_documented_tolerance():
    store = ChunkStore(chunk_size=8, quant="int8", default_tier="host")
    eng = _real_engine(store, system="lmcache")
    reqs = [Request("r0", 0.0, 32, 0, decode_len=0)]
    eng.serve(reqs, verify=False)      # default verify atol is for exact mode
    ex = eng.executor
    tol = store.quant_tolerance()
    errs = ex.verify("r0", atol=tol)
    assert 0 < max(errs[f] for f in ("k", "v")) <= tol


def test_real_lifecycle_with_store_and_quant_finishes_verified():
    """Full cacheflow lifecycle (restore -> prefill -> decode) on the
    materialized store; compute+load mix under a randomized interleaving."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    reqs = [Request("r0", 0.0, 32, 8, decode_len=2),
            Request("r1", 0.1, 24, 8, decode_len=2)]
    rep = eng.serve(reqs, verify=True, op_order="random",
                    rng=np.random.default_rng(1))
    assert set(rep.ttfts) == {"r0", "r1"}
    store.audit()


def test_dedup_hits_skip_transfers_and_reduce_bytes():
    """Two requests sharing an identical prefix: the second one's loads
    are served from the first's HBM-resident chunks — engine-level
    skipped transfers > 0 and no extra bytes move for the shared span."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store, system="lmcache", stages=1)
    # same prefix_len => identical inputs (engine rng reuse) => shared chunks
    eng.serve([Request("a", 0.0, 32, 0, decode_len=0)], verify=True)
    assert store.dedup_hits == 0
    bytes_first = store.bytes_transferred
    assert bytes_first > 0
    eng.serve([Request("b", 0.0, 32, 0, decode_len=0)], verify=True)
    assert store.dedup_hits == 4                # b's chunks deduped to a's
    assert store.skipped_transfers > 0          # engine skipped the channel
    assert store.bytes_transferred == bytes_first   # no new bytes moved
    # and b's cache is still bit-exact
    ex = eng.executor
    ref = ex.store.get("b").kv_reference
    live = ex.live_cache("b")
    for f in ref:
        np.testing.assert_array_equal(np.asarray(live[f]), np.asarray(ref[f]))


def test_sim_hbm_residency_skips_transfer_time():
    """Sim facade residency: prefixes starting in the hbm tier restore
    with zero I/O channel time (dedup/residency hit), strictly faster than
    host-tier starts."""
    cfg = get_config("qwen3-8b")

    def run(kv_tier, rec=None):
        store = TieredKVStore(remote_bw=IO_BANDWIDTHS["10Gbps"])
        eng = SimServingEngine(cfg, HARDWARE["h100"],
                               io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                               system="lmcache", stages=1, max_batch=4,
                               kvstore=store, kv_tier=kv_tier)
        reqs = [Request(f"r{i}", 0.0, 6000, 128, decode_len=4)
                for i in range(4)]
        return eng.run(reqs, trace=rec), store

    rec = TraceRecorder()
    rep_hbm, st_hbm = run("hbm", rec)
    rep_host, st_host = run("host")
    assert st_hbm.io_hits > 0 and st_host.io_hits == 0
    assert np.mean(list(rep_hbm.ttfts.values())) < \
        np.mean(list(rep_host.ttfts.values()))
    # a residency-hit schedule (zero-duration transfers) replays
    # bit-identically even though the replay core has no kvstore: the hit
    # is encoded purely as a pinned gate answer + 0-second dispatch
    assert replay_trace(rec.trace) == rec.trace.captured_result()


# ---------------------------------------------------------------------------
# Eviction-mode preemption: drop + restart from the store (ROADMAP item)
# ---------------------------------------------------------------------------


def test_evicted_then_restarted_request_finishes_verified():
    """preempt + evict: the victim's partially-restored cache is dropped,
    its plans reset, and after re-admission it restores FROM THE STORE and
    finishes with a verified cache and the right greedy tokens."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store, max_batch=1, preempt="priority", evict=True)
    reqs = [Request("bg", 0.0, 48, 8, decode_len=3, priority=0),
            Request("hi", 0.3, 16, 8, decode_len=3, priority=1),
            Request("bg2", 0.4, 40, 8, decode_len=3, priority=0)]
    rec = TraceRecorder()
    rep = eng.serve(reqs, verify=True, op_order="random",
                    rng=np.random.default_rng(3), trace=rec)
    assert sum(rep.preemptions.values()) > 0, "scenario produced no preemption"
    assert rec.trace.meta["evict"] is True
    for r in reqs:
        assert eng.executor.outputs(r.request_id)["tokens"], r.request_id
    # the evict-mode trace replays bit-identically (schema v4 meta)
    assert replay_trace(rec.trace) == rec.trace.captured_result()


def test_sim_evict_mode_matches_roadmap_semantics():
    """Sim engine: with evict=True the preempted victim restarts (strictly
    more total restoration work than park mode), yet everything finishes."""
    from repro.core.cost_model import CostModel
    from repro.core.engine_core import EngineCore, EngineRequest, SimBackend
    from repro.config import ModelConfig

    cfg = ModelConfig(name="t", family="dense", num_layers=8, d_model=256,
                      num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512,
                      vocab_size=1024)
    cost = CostModel(cfg, HARDWARE["h100"], IO_BANDWIDTHS["10Gbps"])

    def run(evict):
        core = EngineCore(SimBackend(cost), stages=1, io_channels=1,
                          max_active=1, preempt="priority", evict=evict,
                          strict=True)
        reqs = [EngineRequest("bg", 16384, 0.0,
                              plans=_plans("bg", 16384), priority=0),
                EngineRequest("hi", 1024, 1e-4,
                              plans=_plans("hi", 1024), priority=1)]
        return core.run(reqs)

    def _plans(rid, n):
        from repro.core.plans import make_request_plans
        return make_request_plans(rid, n, chunk_size=512, l_delta=0,
                                  num_layers=cfg.num_layers)

    res_park = run(evict=False)
    res_drop = run(evict=True)
    assert res_park.preemptions and res_drop.preemptions
    assert set(res_drop.finish) == {"bg", "hi"}
    # dropping completed units costs work: the victim finishes no earlier
    assert res_drop.finish["bg"] >= res_park.finish["bg"]
