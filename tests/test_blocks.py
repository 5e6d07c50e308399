"""Paged-block KV cache (DESIGN.md §12): block pool refcounting,
copy-on-write session forking, block-granular restoration residency, and
the placement-core accounting fixes that rode along.

Covers: pool alloc/free/refcount invariants (incl. double-free detection
and free-list reuse), O(1)-copied-bytes ``clone()``, CoW isolation (a
branch's append never mutates the parent's or the store's bytes), refcount
conservation under randomized fork/append/free interleavings, end-to-end
fork serving with ~zero restoration transfers, block-granular partial
eviction (re-restoration moves only the missing blocks), bit-identical
trace replay of forked schedules, and the PlacementCore regressions:
no-op promote leaves promotions/LRU untouched, integer-exact byte
accounting, and victim ties broken in LRU order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.trace import TraceRecorder, replay_trace
from repro.models import build_model
from repro.models.kvcache import BlockPool, PagedKVCache
from repro.serving import ChunkStore, RealServingEngine, Request
from repro.storage import PlacementCore, Tier

RNG = jax.random.PRNGKey(0)

BS = 4          # block size (tokens) for pure pool/table tests


def _payload(n_tokens, *, seed=0, layers=2, heads=2, dh=3):
    """A small attention-KV payload covering ``n_tokens`` tokens."""
    r = np.random.default_rng(seed)
    return {
        "k": jnp.asarray(r.normal(size=(layers, 1, n_tokens, heads, dh)),
                         jnp.float32),
        "v": jnp.asarray(r.normal(size=(layers, 1, n_tokens, heads, dh)),
                         jnp.float32),
        "kpos": jnp.arange(n_tokens, dtype=jnp.int32)[None].repeat(layers, 0),
    }


# ---------------------------------------------------------------------------
# BlockPool: refcount lifecycle
# ---------------------------------------------------------------------------


def test_pool_alloc_read_roundtrip_and_tail_padding():
    pool = BlockPool(BS, capacity=2)
    full = _payload(BS)
    bid = pool.alloc(full)
    got = pool.read(bid)
    np.testing.assert_array_equal(got["k"], full["k"])
    np.testing.assert_array_equal(got["kpos"], full["kpos"])
    # a short (tail) payload pads to one block: zeros for KV, -1 for kpos
    tail = pool.alloc(_payload(BS - 2, seed=1))
    got = pool.read(tail)
    assert got["k"].shape[2] == BS
    np.testing.assert_array_equal(np.asarray(got["k"])[:, :, BS - 2:], 0.0)
    assert (np.asarray(got["kpos"])[:, BS - 2:] == -1).all()
    pool.audit()


def test_pool_refcount_free_and_reuse():
    pool = BlockPool(BS, capacity=2)
    a = pool.alloc(_payload(BS))
    pool.incref(a)
    pool.decref(a)
    assert pool.live_blocks() == 1       # still one ref
    pool.decref(a)
    assert pool.live_blocks() == 0 and pool.frees == 1
    b = pool.alloc(_payload(BS, seed=2))
    assert b == a                        # freed slot is reused
    pool.audit()


def test_pool_double_free_raises():
    pool = BlockPool(BS)
    a = pool.alloc(_payload(BS))
    pool.decref(a)
    with pytest.raises(AssertionError, match="double free"):
        pool.decref(a)
    with pytest.raises(AssertionError, match="incref of free"):
        pool.incref(a)


def test_pool_write_to_shared_block_refused():
    """write_slice is the sole-owner primitive: callers must CoW first."""
    pool = BlockPool(BS)
    a = pool.alloc(_payload(BS))
    pool.incref(a)
    with pytest.raises(AssertionError, match="shared block"):
        pool.write_slice(a, 0, 1, _payload(1))


def test_pool_grows_past_initial_capacity():
    pool = BlockPool(BS, capacity=1)
    bids = [pool.alloc(_payload(BS, seed=i)) for i in range(5)]
    assert len(set(bids)) == 5 and pool.capacity >= 5
    for i, bid in enumerate(bids):       # slab growth preserved the bytes
        np.testing.assert_array_equal(pool.read(bid)["k"],
                                      _payload(BS, seed=i)["k"])
    pool.audit()


# ---------------------------------------------------------------------------
# PagedKVCache: O(1) fork + copy-on-write
# ---------------------------------------------------------------------------


def test_clone_is_zero_copy_and_aliases_blocks():
    pool = BlockPool(BS)
    parent = PagedKVCache(pool)
    parent.write_span(0, 2 * BS + 1, _payload(2 * BS + 1))
    child = parent.clone()
    assert pool.bytes_copied == 0        # the O(1) fork claim, in bytes
    assert child.blocks == parent.blocks
    assert all(pool.refcounts[b] == 2 for b in child.blocks)
    child.free()
    assert all(pool.refcounts[b] == 1 for b in parent.blocks)
    pool.audit()


def test_cow_isolates_parent_from_child_append():
    """A forked branch appending into the SHARED tail block pays exactly
    one block copy and the parent's bytes stay bit-identical."""
    n = 2 * BS + 1                       # non-block-aligned => shared tail
    pool = BlockPool(BS)
    parent = PagedKVCache(pool)
    parent.write_span(0, n, _payload(n))
    before = {f: np.asarray(a).copy()
              for f, a in parent.read_block(2).items()}
    child = parent.clone()
    child.write_span(n, n + 2, _payload(2, seed=9))
    assert pool.cow_copies == 1
    assert pool.bytes_copied == pool.block_nbytes
    assert child.blocks[2] != parent.blocks[2]   # diverged tail
    assert child.blocks[:2] == parent.blocks[:2]  # full blocks still shared
    after = parent.read_block(2)
    for f in before:
        np.testing.assert_array_equal(before[f], np.asarray(after[f]))
    pool.audit()


def test_aligned_append_opens_fresh_block_no_copy():
    n = 2 * BS                           # block-aligned: nothing shared
    pool = BlockPool(BS)
    parent = PagedKVCache(pool)
    parent.write_span(0, n, _payload(n))
    child = parent.clone()
    child.write_span(n, n + 1, _payload(1, seed=9))
    assert pool.cow_copies == 0 and pool.bytes_copied == 0
    pool.audit()


def test_truncate_drops_tail_refs():
    pool = BlockPool(BS)
    c = PagedKVCache(pool)
    c.write_span(0, 3 * BS, _payload(3 * BS))
    clone = c.clone()
    clone.truncate(BS)                   # keep only the first block
    assert len(clone.blocks) == 1
    assert pool.refcounts[c.blocks[0]] == 2
    assert all(pool.refcounts[b] == 1 for b in c.blocks[1:])
    assert clone.missing_blocks(0, 3 * BS) == [1, 2]
    pool.audit()


@pytest.mark.property
@settings(max_examples=30, deadline=None)
@given(ops=st.lists(st.integers(0, 2), min_size=1, max_size=24),
       n0=st.integers(1, 3 * BS))
def test_refcount_conservation_under_fork_append_free(ops, n0):
    """Random fork/append/free interleavings: every block's refcount equals
    the number of tables mapping it, live+free partitions the pool, and
    freeing every table returns the pool to empty."""
    pool = BlockPool(BS)
    root = PagedKVCache(pool)
    root.write_span(0, n0, _payload(n0))
    tables = [root]
    for i, op in enumerate(ops):
        t = tables[i % len(tables)]
        if op == 0:
            tables.append(t.clone())
        elif op == 1:
            t.write_span(t.n_tokens, t.n_tokens + 3,
                         _payload(3, seed=i))
        elif len(tables) > 1:
            tables.remove(t)
            t.free()
        held = {}
        for tb in tables:
            for b in tb.blocks:
                if b is not None:
                    held[b] = held.get(b, 0) + 1
        assert all(pool.refcounts[b] == n for b, n in held.items())
        assert pool.live_blocks() == len(held)
        pool.audit()
    for t in tables:
        t.free()
    assert pool.live_blocks() == 0
    assert pool.allocs == pool.frees
    pool.audit()


# ---------------------------------------------------------------------------
# End-to-end: fork serving on the materialized store
# ---------------------------------------------------------------------------


def _real_engine(store, **kw):
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    return RealServingEngine(m, params, system=kw.pop("system", "lmcache"),
                             stages=kw.pop("stages", 2), chunk_size=8,
                             kvstore=store, **kw)


def test_forked_branches_restore_with_zero_transfers():
    """Branches carrying meta={'fork_of': parent} alias the parent's
    device blocks: first token with ZERO restoration bytes, verified
    against full-prefill ground truth."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    eng.serve([Request("parent", 0.0, 20, 8, decode_len=2)], verify=True)
    assert store.bytes_transferred > 0   # parent did restore over the wire
    b0, cow0 = store.bytes_transferred, store.pool.bytes_copied
    branches = [Request(f"b{i}", 0.05 * i, 20, 8, decode_len=2,
                        meta={"fork_of": "parent"}) for i in range(2)]
    eng.serve(branches, verify=True)
    assert store.bytes_transferred == b0         # forks moved NOTHING
    assert store.forks == 2
    # each branch's append CoWs exactly its shared tail block, nothing more
    assert store.pool.bytes_copied - cow0 == 2 * store.pool.block_nbytes
    for r in branches:
        assert eng.executor.outputs(r.request_id)["tokens"], r.request_id
    store.audit()


def test_partial_eviction_refetches_only_missing_blocks():
    """Demote HALF the parent's chunks off-device: a new branch's
    restoration transfers EXACTLY the demoted bytes — block-granular
    residency, not a restart from token 0."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    eng.serve([Request("parent", 0.0, 32, 8, decode_len=2)], verify=True)
    full = store.bytes_transferred
    keys = store.requests["parent"]
    demoted = 0
    for k in keys[len(keys) // 2:]:
        store.core.put(k, "host")
        demoted += store._size(k, "host")
    b0 = store.bytes_transferred
    eng.serve([Request("b0", 0.0, 32, 8, decode_len=2,
                       meta={"fork_of": "parent"})], verify=True)
    moved = store.bytes_transferred - b0
    assert moved == demoted, (moved, demoted)
    assert 0 < moved < full
    store.audit()


def test_fork_prefix_len_mismatch_rejected():
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    eng.serve([Request("parent", 0.0, 16, 8, decode_len=2)], verify=True)
    with pytest.raises(ValueError, match="fork"):
        eng.serve([Request("bad", 0.0, 24, 8, decode_len=2,
                           meta={"fork_of": "parent"})])


def test_forked_schedule_replays_bit_identically():
    """Block-granular residency (missing_fraction partial pricing) keeps
    the trace contract: a captured fork schedule replays analytically to
    the exact same EngineResult."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    eng.serve([Request("parent", 0.0, 24, 8, decode_len=2)], verify=True)
    keys = store.requests["parent"]
    store.core.put(keys[-1], "host")     # partially-resident fork source
    rec = TraceRecorder()
    eng.serve([Request(f"b{i}", 0.05 * i, 24, 8, decode_len=2,
                       meta={"fork_of": "parent"}) for i in range(2)],
              verify=True, trace=rec)
    assert replay_trace(rec.trace) == rec.trace.captured_result()


def test_agentic_tree_workload_shape():
    from repro.serving.workloads import generate
    reqs = generate("agentic_tree", 13, seed=3)
    assert len(reqs) == 13
    assert [r.arrival for r in reqs] == sorted(r.arrival for r in reqs)
    roots = {r.request_id for r in reqs if not r.meta}
    for r in reqs:
        if r.meta:
            parent = r.meta["fork_of"]
            assert parent in roots
            parent_req = next(p for p in reqs if p.request_id == parent)
            assert r.prefix_len == parent_req.prefix_len
            assert r.arrival > parent_req.arrival   # branch after its root


# ---------------------------------------------------------------------------
# PlacementCore regressions (satellite fixes)
# ---------------------------------------------------------------------------


def test_promote_that_cannot_move_up_is_pure_noop():
    """An entry too big for every tier in [to, src) must not count a
    promotion or reset its LRU position."""
    core = PlacementCore([Tier("hot", 1e9, 100), Tier("cold", 1e6, 1000)])
    core.put("old", "cold", nbytes=300)      # > hot capacity
    core.put("young", "cold", nbytes=10)
    assert core.promote("old", "hot") == "cold"
    assert core.promotions == 0
    # LRU order untouched: "old" is still the eviction-order head
    assert next(iter(core.tiers["cold"].lru)) == "old"
    core.audit()


def test_promote_that_lands_counts_once():
    core = PlacementCore([Tier("hot", 1e9, 100), Tier("cold", 1e6, 1000)])
    core.put("x", "cold", nbytes=60)
    assert core.promote("x", "hot") == "hot"
    assert core.promotions == 1
    assert core.promote("x", "hot") == "hot"     # already there: no-op
    assert core.promotions == 1
    core.audit()


def test_tier_accounting_is_integer_exact():
    """Byte accounting is exact integers — audit tolerates zero drift even
    after many puts/demotions/removals of odd sizes."""
    core = PlacementCore([Tier("hot", 1e9, 10_001), Tier("cold", 1e6, 10**7)])
    for i in range(64):
        core.put(f"k{i}", "hot", nbytes=333 + i)
    for i in range(0, 64, 3):
        core.remove(f"k{i}")
    core.audit()
    for t in core.tiers.values():
        assert isinstance(t.used, int) and isinstance(t.capacity, int)
        assert t.used == sum(t.lru.values())     # exact, no tolerance


def test_victim_ties_break_in_lru_order():
    """With a constant victim_fn the benefit tie must fall back to true
    LRU recency (the incremental stamps) — a touched entry survives."""
    core = PlacementCore([Tier("hot", 1e9, 200), Tier("cold", 1e6, 1000)],
                         victim_fn=lambda k: 0.0)
    core.put("a", "hot", nbytes=90)
    core.put("b", "hot", nbytes=90)
    core.touch("a")                      # "b" is now least-recent
    core.put("c", "hot", nbytes=90)      # someone must go
    assert core.tier_of("b") == "cold"
    assert core.tier_of("a") == "hot"
    assert core.tier_of("c") == "hot"
    core.audit()


def test_chunkstore_missing_fraction_is_bytes_weighted():
    """missing_fraction reflects per-chunk residency: 0 when everything is
    on device, 1 for unknown requests, exact byte ratio in between."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _real_engine(store)
    eng.serve([Request("p", 0.0, 32, 8, decode_len=2)], verify=True)
    span, layers = (0, 32), (0, eng.model.cfg.num_layers)
    assert store.missing_fraction("p", span, layers) == 0.0
    assert store.missing_fraction("ghost", span, layers) == 1.0
    keys = store.requests["p"]
    store.core.put(keys[1], "host")      # 1 of 4 chunks off-device
    frac = store.missing_fraction("p", span, layers)
    assert frac == pytest.approx(0.25)
    assert store.missing_fraction("p", (8, 16), layers) == 1.0
    assert store.missing_fraction("p", (16, 32), layers) == 0.0
