"""DeepSeek-V2-Lite on the served path, against a plain reference (CPU, tiny).

The block is DeepSeek-V2-Lite's: MLA without a q-LoRA, YaRN RoPE, a dense
leading layer, then expert layers whose router scores every routed
expert (top-k from a softmax, no renormalisation) while this chip holds
only a contiguous share of them, plus shared experts.  Checked here:

* prefill and then decoding through ``RealServingEngine`` with a chunk
  store (restoration both loads and recomputes) agree with the full
  forward of ``tests/_mla_moe_reference.py`` by logits;
* the 8 shares of a 64-expert layer, summed, with the shared experts once,
  give the uncut layer;
* YaRN's frequencies and the MLA softmax scale against an independent
  numpy transcription, at DeepSeek-V2-Lite's rope dims (low/high 10/23);
  ``norm_topk_prob`` false against true;
* ``stack_chunk`` over the whole stack (the prefix layer, then one scan)
  equals the per-layer loop;
* the decode step that reads the latent cache in place
  (``Model._decode_latent``) equals the decode through ``stack_chunk``,
  logits and cache;
* the published configuration's parameter count.
"""
import dataclasses
import math
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _mla_moe_reference as ref
from repro.config import MLAConfig
from repro.configs import get_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, rope_freqs
from repro.models.mla import softmax_scale
from repro.serving import ChunkStore, RealServingEngine, Request

BS = 4          # chunk store block size (tokens)
# float32 on the CPU: the program attends over the latent cache with the
# up-projections absorbed into q and into the output, chunk by chunk, and
# the reference decompresses K and V per head over the whole sequence.
# Both are exact in real arithmetic; float32 reassociation leaves a few
# 1e-6 on logits of magnitude near 0.1-1, so 1e-4 is ample and still far
# below what a missing term (a lost YaRN factor, an absent expert's route,
# a renormalised top-k) moves them.
ATOL = 1e-4


def _cfg(**moe):
    """DeepSeek-V2-Lite's block at tiny widths: 16 routed experts of which
    experts 4-7 are held, 3 per token, 2 shared, one dense leading layer,
    YaRN on 16 rope dims."""
    base = get_config("deepseek-v2-lite")
    return dataclasses.replace(
        base, name="deepseek-v2-lite-tiny", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=16, v_head_dim=16),
        moe=dataclasses.replace(
            base.moe, num_experts=16, top_k=3, expert_d_ff=32,
            shared_d_ff=64, dense_d_ff=128, expert_offset=4, experts_held=4,
            **moe))


class _Engine(RealServingEngine):
    """The served engine with given input tokens: each request's prefix,
    then its suffix, in the order ``serve`` asks for them."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.queue = deque()

    def _inputs(self, n):
        toks = self.queue.popleft()
        assert toks.shape[0] == n
        return jnp.asarray(toks)[None]


def test_served_path_matches_the_reference():
    cfg = _cfg()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(3))
    assert m.experts_held == (4, 16, 4)
    store = ChunkStore(chunk_size=BS, quant="none", default_tier="host")
    eng = _Engine(m, params, stages=2, chunk_size=2 * BS, kvstore=store,
                  l_delta=16)
    kinds = []
    run_op = eng.executor.execute_op
    eng.executor.execute_op = lambda op: (kinds.append(op.kind), run_op(op))
    rng = np.random.default_rng(11)
    reqs = [Request("a", 0.0, 37, 5, decode_len=6),
            Request("b", 0.0, 24, 3, decode_len=5)]
    prompts = {}
    for r in reqs:
        pre = rng.integers(0, cfg.vocab_size, r.prefix_len).astype(np.int32)
        suf = rng.integers(0, cfg.vocab_size, r.new_len).astype(np.int32)
        eng.queue.extend([pre, suf])
        prompts[r.request_id] = np.concatenate([pre, suf])
    eng.serve(reqs, verify=True, op_order="alternate")
    # restoration loaded some of the prefix and recomputed the rest
    assert {"load", "compute", "prefill"} <= set(kinds)
    for r in reqs:
        out = eng.executor.outputs(r.request_id)
        assert len(out["tokens"]) == r.decode_len
        seq = np.concatenate([prompts[r.request_id],
                              np.asarray(out["tokens"][:-1], np.int32)])
        want = np.asarray(ref.forward(params, seq, cfg))
        got = [out["first_logits"]] + out["step_logits"]
        n = len(prompts[r.request_id])
        for i, row in enumerate(got):
            np.testing.assert_allclose(np.asarray(row)[0], want[n - 1 + i],
                                       rtol=0, atol=ATOL)
        # the greedy tokens are the reference's best at every position
        assert out["tokens"] == [int(want[n - 1 + i].argmax())
                                 for i in range(r.decode_len)]


def _layer(key, n_experts=64):
    """A tiny 64-expert layer, 6 per token, uncut and dropless (as the
    served model runs it)."""
    cfg = dataclasses.replace(
        _cfg(), moe=dataclasses.replace(_cfg().moe, num_experts=n_experts,
                                        top_k=6, expert_offset=0,
                                        experts_held=0, capacity_factor=0.0))
    return cfg, moe_mod.init_moe(key, cfg, jnp.float32)


def test_expert_shares_sum_to_the_uncut_layer():
    cfg, full = _layer(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, cfg.d_model))
    whole, _ = moe_mod.apply_moe(full, x, cfg)
    shared = ref.ffn(full["shared"], x)
    parts = []
    for s in range(8):
        share = dataclasses.replace(cfg.moe, expert_offset=8 * s,
                                    experts_held=8)
        p = {k: (v[8 * s:8 * s + 8] if k in ("w_gate", "w_up", "w_down")
                 else v) for k, v in full.items()}
        y, _ = moe_mod.apply_moe(p, x, dataclasses.replace(cfg, moe=share))
        parts.append(y - shared)          # each share adds the shared once
    total = sum(parts) + shared
    np.testing.assert_allclose(total, whole, rtol=0, atol=1e-5)
    # and the reference's uncut layer agrees
    h = x.reshape(-1, cfg.d_model)
    want = ref.routed(full, h, cfg.moe) + ref.ffn(full["shared"], h)
    np.testing.assert_allclose(total.reshape(-1, cfg.d_model), want,
                               rtol=0, atol=1e-5)
    # a route to an expert held elsewhere adds nothing: one share alone
    # differs from the whole
    assert np.abs(parts[0] + shared - whole).max() > 1e-3


def test_norm_topk_prob_false_leaves_the_softmax_weights():
    cfg, full = _layer(jax.random.PRNGKey(7))
    h = jax.random.normal(jax.random.PRNGKey(8), (5, cfg.d_model))
    probs, top_p, top_i = moe_mod.route(full, h, cfg.moe)
    assert not cfg.moe.norm_topk_prob
    np.testing.assert_allclose(
        top_p, jnp.take_along_axis(probs, top_i, axis=1), rtol=1e-6)
    assert float(top_p.sum(-1).max()) < 1.0
    renorm = dataclasses.replace(cfg.moe, norm_topk_prob=True)
    _, top_r, top_ri = moe_mod.route(full, h, renorm)
    np.testing.assert_array_equal(top_ri, top_i)
    np.testing.assert_allclose(top_r.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(top_r, top_p / top_p.sum(-1, keepdims=True),
                               rtol=1e-6)


def _numpy_yarn(d, theta, factor, L0, bf, bs):
    """A transcription of DeepSeek's ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask`` in numpy, float64."""
    def corr(rot):
        return (d * np.log(L0 / (rot * 2 * np.pi))) / (2 * np.log(theta))
    low = max(int(np.floor(corr(bf))), 0)
    high = min(int(np.ceil(corr(bs))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    pos_freqs = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    return low, high, inter * (1 - mask) + extra * mask


def test_yarn_frequencies_and_mla_scale_match_the_published_formula():
    cfg = get_config("deepseek-v2-lite")
    y = cfg.rope_scaling
    low, high, want = _numpy_yarn(64, 10_000.0, 40.0, 4096, 32.0, 1.0)
    assert (low, high) == (10, 23)
    assert ref.yarn_bounds(64, 10_000.0, 4096, 32.0, 1.0) == (10, 23)
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 10_000.0, y)),
                               want, rtol=1e-6)
    # pairs below `low` keep the plain frequency, from `high` on they are
    # stretched by the factor
    plain = np.asarray(rope_freqs(64, 10_000.0))
    np.testing.assert_allclose(want[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(want[23:], plain[23:] / 40.0, rtol=1e-6)
    # softmax scale: 192^-1/2 · (0.1·0.707·ln 40 + 1)^2
    g = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * g * g, rel=1e-12)
    assert softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-6)
    # mscale = mscale_all_dim: cos and sin are not scaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 2, 64))
    pos = jnp.arange(7)[None]
    rot = apply_rope(x, pos, 10_000.0, y)
    np.testing.assert_allclose(np.linalg.norm(rot, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(
        rot[0], ref.rope(x[0], pos[0], cfg), rtol=1e-5, atol=1e-5)
    # where mscale differs from mscale_all_dim, cos and sin scale by the
    # ratio of the two magnitude factors
    y2 = dataclasses.replace(y, mscale=1.0)
    r2 = apply_rope(x, pos, 10_000.0, y2)
    ratio = (0.1 * math.log(40.0) + 1.0) / g
    np.testing.assert_allclose(r2, rot * ratio, rtol=1e-5, atol=1e-6)
    # without YaRN the frequencies and scale are the plain ones
    plain_cfg = dataclasses.replace(cfg, rope_scaling=None)
    assert softmax_scale(plain_cfg) == 1.0 / 192 ** 0.5


def test_prefix_then_scan_equals_the_layer_loop():
    cfg = _cfg()
    m = build_model(cfg)
    assert m.prefix_len == 1 and m.scan_len == 2
    params = m.init(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                                cfg.vocab_size)
    cache = m.init_cache(1, 8)
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    x = m.embed(params, tokens, pos)
    y_scan, c_scan = m.stack_chunk(params, x, pos, dict(cache))
    y_loop, c_loop = x, dict(cache)
    for i in range(cfg.num_layers):
        y_loop, c_loop = m.layer_chunk(params, i, y_loop, pos, c_loop)
    np.testing.assert_allclose(y_scan, y_loop, rtol=1e-5, atol=1e-5)
    assert c_scan.keys() == c_loop.keys()
    for f in c_scan:
        np.testing.assert_allclose(c_scan[f], c_loop[f], rtol=1e-5,
                                   atol=1e-5)
    # the whole-stack decode program scans: one layer body, not three
    jaxpr = jax.make_jaxpr(m.decode_step)(params, tokens[:, 0], c_scan, 6)
    assert "scan" in str(jaxpr)


def test_latent_decode_equals_the_stack_decode():
    cfg = _cfg()
    m = build_model(cfg)
    assert m.latent_decode
    params = m.init(jax.random.PRNGKey(3))
    n, steps = 6, 4
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, n + steps), 0,
                                cfg.vocab_size)
    _, cache = m.prefill_chunk(params, tokens[:, :n], m.init_cache(1, 12), 0)
    latent, stacked = dict(cache), dict(cache)
    for t in range(n, n + steps):
        got, latent = m.decode_step(params, tokens[:, t], latent, t)
        m.latent_decode = False
        try:
            want, stacked = m.decode_step(params, tokens[:, t], stacked, t)
        finally:
            m.latent_decode = True
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert latent.keys() == stacked.keys()
    for f in latent:
        np.testing.assert_allclose(latent[f], stacked[f], rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(latent["kpos"][:, :n + steps],
                                  np.arange(n + steps)[None].repeat(3, 0))
    assert (np.asarray(latent["kpos"][:, n + steps:]) == -1).all()


def test_published_parameter_count():
    pc = get_config("deepseek-v2-lite").param_counts()
    assert abs(pc["total"] - 15.7e9) / 15.7e9 < 0.01
    assert abs(pc["active"] - 2.4e9) / 2.4e9 < 0.12
