"""Donated decode programs (``RestorationExecutor.decode_step_batch``).

Each decode token runs as one jitted forward that donates the request's
cache and returns the greedy next token on the device, then one jitted
write of the token's KV into its pool block that donates the pool's
slabs.  The step launches every request's programs before it reads any
token back.  Checked on the CPU at tiny sizes
against the eager per-token path it replaced (forward, ``_paged_write``
through the block table, argmax on the host):

  * the same tokens, step logits and pool-block bytes, over decodes that
    cross a block boundary, for dense GQA and MLA caches on a chunk store
    and for hybrid, RWKV (no ``kpos``, no pool write) and embedding-input
    models without one;
  * a forked sibling writing into the tail block it shares with its
    parent gets its own copy, and the parent's bytes stay as they were;
  * decode steps at one cache length compile no forward after the first,
    and nothing at all while the pool's capacity holds;
  * ``decode.fused_tokens_total`` counts every decode token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.kvcache import grow_cache
from repro.obs import spans
from repro.serving import ChunkStore, RealServingEngine, Request

BS = 4          # chunk store block size (tokens)

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        m = build_model(get_config(arch).reduced())
        _MODELS[arch] = (m, m.init(jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _engine(arch, *, eager=False, telemetry=None):
    m, params = _model(arch)
    cfg = m.cfg
    store = None
    if cfg.num_kv_heads and not cfg.attn_window and cfg.rwkv is None:
        store = ChunkStore(chunk_size=BS, quant="none", default_tier="host")
    eng = RealServingEngine(m, params, stages=2, chunk_size=2 * BS,
                            kvstore=store, telemetry=telemetry)
    if eager:
        ex = eng.executor
        ex.decode_step_batch = lambda rids: _eager_step(ex, rids)
    return eng


def _eager_step(ex, rids):
    """The per-token path that the donated programs replaced: an eager
    forward, the new token's KV written through the block table with
    ``_paged_write``, the argmax read back on the host."""
    m, cfg = ex.model, ex.model.cfg
    for rid in rids:
        live = ex._live[rid]
        req = live["req"]
        if "pos" not in live:
            _, decode_len = ex._suffix.get(rid, (None, 0))
            live["cache"] = grow_cache(cfg, live["cache"],
                                       req.n_tokens + max(1, decode_len))
            live["last_logits"] = req.final_logits
            live["tokens_out"], live["step_logits"] = [], []
            live["pos"] = req.n_tokens
        if cfg.input_mode == "tokens":
            inp = jnp.argmax(live["last_logits"], axis=-1).astype(jnp.int32)
        else:
            key = jax.random.fold_in(jax.random.PRNGKey(0), live["pos"])
            inp = jax.random.normal(key, (1, cfg.d_model), jnp.float32)
        logits, live["cache"] = m.decode_step_jit(ex.params, inp,
                                                  live["cache"], live["pos"])
        live["last_logits"] = logits
        if "kpos" in live["cache"]:
            ex._paged_write(live, live["pos"], live["pos"] + 1)
        live["pos"] += 1
        live["tokens_out"].append(int(jnp.argmax(logits[0])))
        live["step_logits"].append(logits)


def _blocks(eng, rid):
    """The bytes of each logical block of the request's table."""
    paged = eng.executor.paged_cache(rid)
    if paged is None:
        return None
    return [{f: np.asarray(a) for f, a in paged.pool.read(b).items()}
            for b in paged.blocks]


def _assert_same_outputs(fused, eager, rid):
    a, b = fused.executor.outputs(rid), eager.executor.outputs(rid)
    assert a["tokens"] == b["tokens"], rid
    assert len(a["step_logits"]) == len(b["step_logits"])
    for x, y in zip(a["step_logits"], b["step_logits"]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


def _assert_same_blocks(fused, eager, rid):
    a, b = _blocks(fused, rid), _blocks(eager, rid)
    assert (a is None) == (b is None)
    if a is None:
        return
    assert len(a) == len(b)
    for blk_a, blk_b in zip(a, b):
        assert blk_a.keys() == blk_b.keys()
        for f in blk_a:
            if f == "kpos":
                np.testing.assert_array_equal(blk_a[f], blk_b[f])
            else:
                np.testing.assert_allclose(blk_a[f], blk_b[f],
                                           rtol=1e-5, atol=1e-5)


def _decoded(requests):
    """Tokens the decode steps produce: a suffix's first token comes from
    its prefill."""
    return sum(r.decode_len - (r.new_len > 0) for r in requests)


# the suffix lifecycles decode from positions 26 and 18, the decode-only
# one from 18: each crosses a block boundary
_REQUESTS = [Request("r0", 0.0, 22, 4, decode_len=7),
             Request("r1", 0.0, 14, 4, decode_len=6),
             Request("d0", 0.0, 18, 0, decode_len=6)]


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-v2-236b",
                                  "recurrentgemma-2b", "rwkv6-7b",
                                  "musicgen-large"])
def test_decode_programs_match_the_eager_path(arch):
    fused, eager = _engine(arch), _engine(arch, eager=True)
    for eng in (fused, eager):
        eng.serve([Request(r.request_id, r.arrival, r.prefix_len, r.new_len,
                           decode_len=r.decode_len) for r in _REQUESTS],
                  verify=True, op_order="alternate")
    for r in _REQUESTS:
        _assert_same_outputs(fused, eager, r.request_id)
        _assert_same_blocks(fused, eager, r.request_id)
        assert len(fused.executor.outputs(r.request_id)["tokens"]) \
            == r.decode_len
    store = fused.kvstore
    if arch in ("qwen3-8b", "deepseek-v2-236b"):
        # the paged path ran, and did the bookkeeping the eager path did
        assert store is not None and fused.executor.paged_cache("r0")
        for key in ("allocs", "frees", "cow_copies", "capacity"):
            assert getattr(store.pool, key) == getattr(eager.kvstore.pool,
                                                       key), key
        store.audit()
    assert fused.executor.fused_tokens == _decoded(_REQUESTS)


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-v2-236b"])
def test_forked_sibling_copies_the_shared_tail_block(arch):
    """A decode-only parent writes its decoded tokens into the tail block
    of its 18-token prefix; a fork of the live parent inherits that block
    and its first decode token copies it before writing."""
    fused, eager = _engine(arch), _engine(arch, eager=True)
    for eng in (fused, eager):
        eng.serve([Request("p", 0.0, 18, 0, decode_len=3)], verify=True,
                  op_order="alternate")
    pool = fused.kvstore.pool
    tail = fused.executor.paged_cache("p").blocks[18 // BS]
    parent_before = _blocks(fused, "p")
    cow0 = pool.cow_copies
    for eng in (fused, eager):
        eng.serve([Request("c", 0.0, 18, 0, decode_len=3,
                           meta={"fork_of": "p"})], verify=True,
                  op_order="alternate")
    child = fused.executor.paged_cache("c")
    assert child.blocks[18 // BS] != tail
    assert pool.cow_copies == cow0 + 1
    assert fused.executor.paged_cache("p").blocks[18 // BS] == tail
    after = _blocks(fused, "p")
    for blk_a, blk_b in zip(parent_before, after):
        for f in blk_a:
            np.testing.assert_array_equal(blk_a[f], blk_b[f])
    for rid in ("p", "c"):
        _assert_same_outputs(fused, eager, rid)
        _assert_same_blocks(fused, eager, rid)
    fused.kvstore.audit()


def test_steps_at_one_cache_length_compile_nothing_after_the_first():
    """r0 warms up; r1, at the same lengths, enters decode with its cache
    and first token uncommitted to a device (as after a restoration that
    loaded nothing) while the pool's slabs are committed: its forward
    compiles nothing either.  The pool write compiles only where the pool
    grew (the slab capacity is its program's shape)."""
    eng = _engine("qwen3-8b")
    ex, pool = eng.executor, eng.kvstore.pool
    inner_step, inner_op = ex.decode_step_batch, ex.execute_op
    log = spans.log()
    steps = []          # (rid, compiled functions, pool capacity, position)

    def step(rids):
        n = len(log.records)
        inner_step(rids)
        names = [r[5]["fun_name"] for r in log.records[n:]
                 if r[0] == "compile"]
        steps.append((rids[0], names, pool.capacity,
                      ex._live[rids[0]]["pos"]))

    def execute_op(op):
        inner_op(op)
        live = ex._live[op.request_id]
        if op.request_id == "r1" and "next_token" in live:
            for k in ("cache", "next_token"):
                live[k] = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                                       live[k])

    ex.decode_step_batch, ex.execute_op = step, execute_op
    prev = spans.force_recording(True)
    log.clear()
    try:
        for rid in ("r0", "r1"):
            eng.serve([Request(rid, 0.0, 16, 4, decode_len=12)],
                      verify=True, op_order="alternate")
            ex.release(rid)
    finally:
        spans.force_recording(prev)
        log.clear()
    assert len(steps) == 22
    # positions 20..30 of each request; the steps at 24 and 28 open a block
    assert [s[3] for s in steps] == 2 * list(range(21, 32))
    assert any("_decode_program" in n for n in steps[0][1])
    for (_, names, cap, _), (_, _, cap0, _) in zip(steps[1:], steps):
        assert not any("_decode_program" in n for n in names), names
        if cap == cap0:
            assert names == []
    assert sum(cap == cap0 for (rid, _, cap, _), (_, _, cap0, _)
               in zip(steps[1:], steps) if rid == "r1") >= 8


def test_fused_tokens_are_counted_in_telemetry():
    eng = _engine("qwen3-8b", telemetry=True)
    rep = eng.serve([Request(r.request_id, r.arrival, r.prefix_len,
                             r.new_len, decode_len=r.decode_len)
                     for r in _REQUESTS], verify=True,
                    op_order="alternate")
    decoded = _decoded(_REQUESTS)
    counters = rep.telemetry["metrics"]["counters"]
    assert counters["decode.fused_tokens_total"] == decoded
    names = [s["name"] for s in rep.telemetry["spans"]]
    assert names.count("decode.forward") == decoded
    assert names.count("decode.readback") == decoded
