"""Fused restoration data path (DESIGN.md §13).

Covers: fused-vs-legacy cache equivalence (bit-exact for quant="none",
within the documented tolerance for int8) with byte-identical store
accounting; strictly fewer copy dispatches on the fused path; the
double-buffered transfer stream's depth bound, backpressure and
serial-equivalence (depth=1 ≡ depth=2 caches); the int8 shadow keeping
demote/promote cycles drift-free; channel→device routing through the
sharding mesh helper; engine-level serving through the fused path with
verification and bit-identical trace replay."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.baselines import make_baseline_plans
from repro.core.datapath import RestoreDatapath, TransferStream
from repro.core.executor import RestorationExecutor
from repro.core.trace import TraceRecorder, replay_trace
from repro.models import build_model
from repro.serving import ChunkStore, RealServingEngine, Request

RNG = jax.random.PRNGKey(0)

_MODEL = {}


def _model():
    if not _MODEL:
        cfg = get_config("qwen3-8b").reduced()
        m = build_model(cfg)
        _MODEL.update(cfg=cfg, model=m, params=m.init(RNG))
    return _MODEL


def _executor(*, datapath, quant="none", store_chunk=8, tier="host",
              depth=2, stages=1):
    mm = _model()
    store = ChunkStore(chunk_size=store_chunk, quant=quant,
                      default_tier=tier)
    dp = RestoreDatapath.for_channels(1, depth=depth) if datapath else None
    ex = RestorationExecutor(mm["model"], mm["params"], chunk_size=16,
                             stages=stages, chunk_store=store, datapath=dp)
    return ex, store


def _restore(ex, rid="r", n=40, op_order="alternate", rng=None):
    plans = make_baseline_plans("lmcache", rid, n, chunk_size=16, l_delta=0,
                                num_layers=_model()["cfg"].num_layers)
    return ex.restore(rid, plans=plans, op_order=op_order, rng=rng)


def _remember(ex, rid="r", n=40):
    inputs = jax.random.randint(jax.random.fold_in(RNG, n), (1, n), 0,
                                _model()["cfg"].vocab_size)
    ex.remember(rid, inputs)


# ---------------------------------------------------------------------------
# Fused vs legacy: caches and accounting
# ---------------------------------------------------------------------------


def test_fused_bit_identical_to_legacy_and_reference():
    """quant="none": the fused packed-staging + scatter path restores the
    exact bits of both the legacy per-chunk path and the full-prefill
    reference, with byte-identical store accounting and strictly fewer
    dispatched copy ops."""
    exL, stL = _executor(datapath=False)
    _remember(exL)
    cacheL = _restore(exL)
    exF, stF = _executor(datapath=True)
    _remember(exF)
    cacheF = _restore(exF)
    for f in cacheL:
        np.testing.assert_array_equal(np.asarray(cacheL[f]),
                                      np.asarray(cacheF[f]))
    exF.verify("r")                      # strict vs kv_reference
    assert exF.fused_loads > 0 and exF.legacy_loads == 0
    assert exL.fused_loads == 0
    # accounting parity: same bytes, fetches, hits either way
    assert stF.bytes_transferred == stL.bytes_transferred > 0
    assert stF.fetches == stL.fetches
    assert stF.io_hits == stL.io_hits
    assert stF.store_misses == stL.store_misses == 0
    # the tentpole perf claim at op granularity
    assert exF.load_dispatches < exL.load_dispatches
    stF.audit(), stL.audit()


def test_fused_int8_within_tolerance_and_half_bytes():
    """int8 chunks cross the wire quantized (scales ride along) and the
    kernel dequantizes on device: restored cache within quant_tolerance,
    wire bytes ≈ the quantized encoding (about half of fp16)."""
    exQ, stQ = _executor(datapath=True, quant="int8")
    _remember(exQ)
    _restore(exQ)
    exQ.verify("r", atol=2e-2 + stQ.quant_tolerance())
    exN, stN = _executor(datapath=True)
    _remember(exN)
    _restore(exN)
    itemsize = np.dtype(_model()["model"].compute_dtype).itemsize
    fp16_equiv = stN.bytes_transferred * 2 / itemsize
    assert 0.4 < stQ.bytes_transferred / fp16_equiv < 0.75
    # legacy int8 moves the same bytes (the decode point moved, not the
    # wire format)
    exQL, stQL = _executor(datapath=False, quant="int8")
    _remember(exQL)
    _restore(exQL)
    assert stQL.bytes_transferred == stQ.bytes_transferred
    stQ.audit()


def test_fused_random_interleavings_match_reference():
    """Property: fused restoration is correct under ANY legal op
    interleaving (mixed compute/load claims), same as the legacy path."""
    ex, store = _executor(datapath=True)
    _remember(ex, n=56)
    for seed in range(3):
        if ex.is_live("r"):
            ex.drop_restore("r")
        plans = make_baseline_plans("cacheflow", "r", 56, chunk_size=16,
                                    l_delta=32,
                                    num_layers=_model()["cfg"].num_layers)
        ex.restore("r", plans=plans, op_order="random",
                   rng=np.random.default_rng(seed))
        ex.verify("r")


def test_fused_resident_rerun_is_device_local():
    """A second restoration of the same prefix finds every chunk HBM-
    resident: the fused path copies out of the pool views (io hits, no
    wire bytes, no staging puts)."""
    ex, store = _executor(datapath=True)
    _remember(ex)
    _restore(ex)
    b0, p0 = store.bytes_transferred, sum(s.puts for s in ex.datapath.streams)
    ex.drop_restore("r")
    _restore(ex)
    ex.verify("r")
    assert store.bytes_transferred == b0          # nothing crossed the wire
    assert sum(s.puts for s in ex.datapath.streams) == p0
    assert ex.datapath.resident_copies > 0
    assert store.io_hits > 0


# ---------------------------------------------------------------------------
# Transfer stream: depth bound, backpressure, serial equivalence
# ---------------------------------------------------------------------------


def test_transfer_stream_depth_bound():
    s = TransferStream(depth=2)
    for i in range(5):
        s.put({"x": np.full((4, 4), i, np.float32)})
        assert len(s._inflight) <= 2
    assert s.puts == 5
    assert s.bytes_staged == 5 * 4 * 4 * 4
    s.sync()
    assert not s._inflight


def test_double_buffered_pipeline_matches_serial():
    """Overlap test: depth=2 (op k+1's host→device copy in flight under
    op k's scatter) produces caches bit-identical to the fully serial
    depth=1 stream."""
    ex1, _ = _executor(datapath=True, depth=1)
    _remember(ex1, n=64)
    c1 = _restore(ex1, n=64)
    ex2, _ = _executor(datapath=True, depth=2)
    _remember(ex2, n=64)
    c2 = _restore(ex2, n=64)
    for f in c1:
        np.testing.assert_array_equal(np.asarray(c1[f]), np.asarray(c2[f]))
    ex2.verify("r")


# ---------------------------------------------------------------------------
# int8 shadow: same-precision tier moves keep the quantized payload
# ---------------------------------------------------------------------------


def test_promote_keeps_int8_shadow_no_requant_drift():
    """Promote→demote cycles of a quantized chunk must reuse the
    authoritative int8 encoding (shadowed across the promote) instead of
    requantizing the decoded bf16 view — payload stays bit-stable over
    arbitrarily many cycles."""
    ex, store = _executor(datapath=True, quant="int8")
    _remember(ex)
    key = store.requests["r"][0]
    ref = {f: np.array(store._host_payload(key)[f]["q"])
           for f in store.chunks[key].fields}
    for _ in range(3):
        got = store.fetch_packed(key)           # promotes via fused path?
        if got[0] != "hbm":
            # land it on device the way the datapath would
            dev = store._decode_device(key)
            store.promote_staged(key, dev)
        assert store.core.tier_of(key) == "hbm"
        assert "host" in store.chunks[key].reprs      # the shadow
        store.core.put(key, "host")                   # demote back
        pay = store._host_payload(key)
        for f, q in ref.items():
            np.testing.assert_array_equal(np.asarray(pay[f]["q"]), q)
    store.audit()


def test_quant_none_promote_drops_stale_reprs():
    """Without quantization there is no shadow: tier moves keep exactly
    one authoritative repr (memory hygiene regression guard)."""
    ex, store = _executor(datapath=True, quant="none")
    _remember(ex)
    key = store.requests["r"][0]
    store.fetch(key)
    assert set(store.chunks[key].reprs) == {"hbm"}
    store.core.put(key, "host")
    assert set(store.chunks[key].reprs) == {"host"}


# ---------------------------------------------------------------------------
# Channel → device routing
# ---------------------------------------------------------------------------


def test_io_channel_devices_and_stream_routing():
    from repro.distributed.sharding import io_channel_devices
    devs = io_channel_devices(None, 3)
    assert len(devs) == 3 and all(d is not None for d in devs)
    dp = RestoreDatapath.for_channels(3)
    assert len(dp.streams) == 3
    assert all(s.device is not None for s in dp.streams)
    assert dp.stream_for(0) is dp.streams[0]
    assert dp.stream_for(4) is dp.streams[1]      # modulo wrap


@pytest.mark.parametrize("backend,kv_heads,block,pallas", [
    ("auto", 2, 8, False),        # CPU: the jitted oracle
    ("pallas", 2, 4, False),      # 4-token blocks, not sublane-aligned: oracle
    ("interpret", 2, 8, True),    # 64 channels: the kernel runs
    ("interpret", 4, 8, True),    # 128 channels: the kernel runs
], ids=["auto-cpu", "unaligned", "narrow", "aligned"])
def test_staged_runs_counted_by_what_scattered_them(backend, kv_heads,
                                                    block, pallas):
    """Every staged run counts once, as a Pallas launch or as an oracle
    run — an unaligned shape that falls back to the oracle is never
    counted as a kernel launch.  Any channel count takes the kernel."""
    cfg = get_config("qwen3-8b").reduced(num_kv_heads=kv_heads)
    m = build_model(cfg)
    store = ChunkStore(chunk_size=block, default_tier="host")
    dp = RestoreDatapath.for_channels(1, backend=backend)
    ex = RestorationExecutor(m, m.init(RNG), chunk_size=16,
                             chunk_store=store, datapath=dp)
    inputs = jax.random.randint(RNG, (1, 40), 0, cfg.vocab_size)
    ex.remember("r", inputs)
    plans = make_baseline_plans("lmcache", "r", 40, chunk_size=16, l_delta=0,
                                num_layers=cfg.num_layers)
    ex.restore("r", plans=plans)
    ex.verify("r")
    staged = sum(s.puts for s in dp.streams)
    assert staged > 0
    assert dp.pallas_launches + dp.oracle_runs == staged
    assert dp.pallas_launches == (staged if pallas else 0)


def test_engine_channel_hint_reaches_executor():
    from repro.core.engine_core import RealBackend
    ex, _ = _executor(datapath=True)
    backend = RealBackend(ex)
    assert ex.datapath.measure is True            # measured mode
    backend.io_channel_hint(1)
    assert ex.io_channel == 1


# ---------------------------------------------------------------------------
# Engine-level serving + trace replay
# ---------------------------------------------------------------------------


def _engine(store, **kw):
    mm = _model()
    return RealServingEngine(mm["model"], mm["params"],
                             system=kw.pop("system", "cacheflow"),
                             stages=kw.pop("stages", 2), chunk_size=8,
                             kvstore=store, **kw)


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_engine_serve_fused_verified(quant):
    """End-to-end: multi-request serving through the fused datapath in
    MEASURED mode (datapath wall secs feed RealBackend.io_secs) passes
    per-request cache verification, measures per-channel bandwidth, and
    matches legacy-path store accounting.  The parity engines run the
    load-only baseline: under cacheflow's two-pointer race WHICH chunks
    load is schedule-dependent (fused and legacy time differently), the
    wrong substrate for byte assertions — see benchmarks/fork.py."""
    store = ChunkStore(chunk_size=8, quant=quant, default_tier="host")
    eng = _engine(store, system="lmcache", datapath="fused", io_channels=2)
    reqs = [Request(f"r{i}", 0.0, 24 + 16 * i, 8, decode_len=2)
            for i in range(3)]
    rep = eng.serve(reqs, verify=True)
    assert eng.executor.fused_loads > 0
    assert all(v > 0 for v in rep.ttfts.values())
    # measured per-channel bandwidth is now an observable
    assert any(b is not None and b > 0 for b in eng.datapath.bandwidths())
    store2 = ChunkStore(chunk_size=8, quant=quant, default_tier="host")
    eng2 = _engine(store2, system="lmcache", datapath="legacy",
                   io_channels=2)
    eng2.serve([Request(f"r{i}", 0.0, 24 + 16 * i, 8, decode_len=2)
                for i in range(3)], verify=True)
    assert eng2.datapath is None and eng2.executor.fused_loads == 0
    assert store.bytes_transferred == store2.bytes_transferred
    assert store.fetches == store2.fetches
    store.audit(), store2.audit()


def test_fused_trace_replays_bit_identically():
    """Scheduler decisions are datapath-independent: a trace captured
    through the fused engine replays bit-identically on the analytic
    replay core (schema v5 unchanged)."""
    store = ChunkStore(chunk_size=8, quant="none", default_tier="host")
    eng = _engine(store, datapath="fused")
    rec = TraceRecorder()
    eng.serve([Request("a", 0.0, 40, 8, decode_len=2),
               Request("b", 0.1, 24, 8, decode_len=2)],
              verify=True, op_order="random",
              rng=np.random.default_rng(0), trace=rec)
    assert replay_trace(rec.trace) == rec.trace.captured_result()
