"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU; same pallas_call lowers to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import ops as fd_ops, ref as fd_ref
from repro.kernels.flash_prefill import ops as fp_ops, ref as fp_ref
from repro.kernels.rglru_scan import ops as rg_ops, ref as rg_ref
from repro.kernels.rwkv6_scan import ops as wk_ops, ref as wk_ref

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,hq,hk,dh,off,window", [
    (1, 128, 128, 4, 4, 64, 0, 0),          # pure causal, MHA
    (2, 128, 384, 4, 2, 64, 256, 0),        # chunk with cached prefix, GQA
    (1, 256, 256, 8, 1, 32, 0, 64),         # MQA, windowed
    (1, 200, 328, 4, 2, 64, 128, 0),        # non-multiple-of-block shapes
])
def test_flash_prefill_matches_ref(dtype, b, sq, skv, hq, hk, dh, off, window):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, skv, hk, dh), dtype)
    v = jax.random.normal(ks[2], (b, skv, hk, dh), dtype)
    scale = 1.0 / np.sqrt(dh)
    ref = fp_ref.flash_prefill_ref(q, k, v, off, skv, scale=scale, window=window)
    out = fp_ops.flash_prefill_attention(q, k, v, off, skv, scale=scale,
                                         window=window, backend="interpret",
                                         bq=128, bk=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hk,dh,valid,window", [
    (2, 512, 8, 2, 64, 300, 0),
    (1, 256, 4, 4, 128, 256, 0),
    (1, 384, 8, 1, 64, 200, 128),            # ring/windowed
])
def test_flash_decode_matches_ref(dtype, b, s, hq, hk, dh, valid, window):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hk, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hk, dh), dtype)
    kpos = jnp.where(jnp.arange(s) < valid, jnp.arange(s), -1).astype(jnp.int32)
    q_pos = valid - 1
    scale = 1.0 / np.sqrt(dh)
    ref = fd_ref.flash_decode_ref(q, k, v, kpos, q_pos, scale=scale, window=window)
    out = fd_ops.flash_decode_attention(q, k, v, kpos, q_pos, scale=scale,
                                        window=window, backend="interpret", bk=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,s,w,bs,bw", [
    (2, 256, 256, 128, 128),
    (1, 512, 128, 256, 128),
    (3, 128, 384, 64, 256),
])
def test_rglru_scan_matches_ref(b, s, w, bs, bw):
    ks = jax.random.split(RNG, 3)
    log_a = -jax.nn.softplus(jax.random.normal(ks[0], (b, s, w)))
    bt = jax.random.normal(ks[1], (b, s, w))
    h0 = jax.random.normal(ks[2], (b, w))
    h_ref, hl_ref = rg_ref.rglru_scan_ref(log_a, bt, h0)
    h, hl = rg_ops.rglru_scan(log_a, bt, h0, backend="interpret", bs=bs, bw=bw)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hl_ref), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("b,s,h,dh,bs", [
    (2, 128, 2, 32, 64),
    (1, 256, 4, 64, 128),
])
def test_rwkv6_scan_matches_ref(b, s, h, dh, bs):
    ks = jax.random.split(RNG, 6)
    r = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, h, dh))
    v = jax.random.normal(ks[2], (b, s, h, dh))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, dh)) - 2))
    u = jax.random.normal(ks[4], (h, dh)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, dh, dh)) * 0.1
    y_ref, sl_ref = wk_ref.wkv6_ref(r, k, v, w, u, s0)
    y, sl = wk_ops.wkv6(r, k, v, w, u, s0, backend="interpret", bs=bs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sl), np.asarray(sl_ref), atol=2e-3, rtol=2e-3)


def test_wkv_chunked_matches_sequential():
    """The chunked wkv (model fast path / kernel structure) == per-token scan."""
    from repro.models.rwkv6 import wkv_scan_chunked, wkv_scan_ref
    ks = jax.random.split(RNG, 6)
    b, s, h, dh = 2, 256, 2, 32
    r, k, v = (jax.random.normal(ks[i], (b, s, h, dh)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, dh)) - 2))
    u = jax.random.normal(ks[4], (h, dh)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, dh, dh)) * 0.1
    y1, sl1 = wkv_scan_ref(r, k, v, w, u, s0)
    y2, sl2 = wkv_scan_chunked(r, k, v, w, u, s0, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sl1), np.asarray(sl2), atol=2e-3, rtol=2e-3)


def test_flash_prefill_is_restoration_primitive():
    """Chunk-with-prefix flash == slicing the full causal result (the
    recompute-pointer step semantics)."""
    b, n, hq, hk, dh = 1, 256, 4, 2, 64
    c0 = 128  # prefix boundary
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, n, hq, dh))
    k = jax.random.normal(ks[1], (b, n, hk, dh))
    v = jax.random.normal(ks[2], (b, n, hk, dh))
    scale = 1 / np.sqrt(dh)
    full = fp_ref.flash_prefill_ref(q, k, v, 0, n, scale=scale)
    chunk = fp_ops.flash_prefill_attention(q[:, c0:], k, v, c0, n, scale=scale,
                                           backend="interpret")
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(full[:, c0:]),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# kv_quant: per-channel int8 quantize/dequantize (storage demotion codec)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (2, 1, 16, 4, 64),        # (n_attn, B, T, Hkv, Dh) attention KV chunk
    (2, 1, 16, 96),           # MLA ckv chunk (no head axis)
    (1, 1, 5, 3, 24),         # ragged tail chunk, non-multiple-of-block dims
    (300, 8),                 # tall-thin 2D (row padding path)
])
def test_kv_quant_kernel_matches_ref(dtype, shape):
    from repro.kernels.kv_quant import ops as kq_ops, ref as kq_ref
    x = jax.random.normal(jax.random.fold_in(RNG, sum(shape)), shape, dtype)
    q_ref, s_ref = kq_ref.kv_quantize_ref(x)
    q, s = kq_ops.kv_quantize(x, backend="interpret")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-6, atol=0)
    y = kq_ops.kv_dequantize(q, s, dtype, backend="interpret")
    y_ref = kq_ref.kv_dequantize_ref(q_ref, s_ref, dtype)
    # 1-ULP slack: interpret-mode lowering may fuse the f32 multiply
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-7, atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_quant_round_trip_error_bound(dtype):
    """|x - deq(quant(x))| <= 0.5*scale (round-off) + 0.5*scale (target-
    dtype recast) per channel — the bound ChunkStore.quant_tolerance
    documents."""
    from repro.kernels.kv_quant import ops as kq_ops
    x = jax.random.normal(jax.random.fold_in(RNG, 7), (4, 1, 32, 2, 16), dtype)
    q, s = kq_ops.kv_quantize(x, backend="ref")
    y = kq_ops.kv_dequantize(q, s, dtype, backend="ref")
    err = np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32))
    bound = np.asarray(s) * (0.5 if dtype == jnp.float32 else 1.0) + 1e-7
    assert (err <= bound).all()


# ---------------------------------------------------------------------------
# kv_restore: fused restoration dequant-scatter (one launch per load op)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("a,s,c,cs,t0,nch", [
    (4, 32, 128, 8, 8, 2),        # aligned mid-prefix range
    (4, 30, 128, 8, 16, 2),       # odd tail: t0+T=32 > S=30 (boundary clip)
    (2, 20, 256, 4, 0, 5),        # whole prefix, many chunks
    (3, 9, 128, 8, 8, 1),        # single tail chunk, 7 padded rows clipped
    (3, 24, 576, 8, 8, 2),       # an MLA latent: 512 + 64, not lane-aligned
])
def test_kv_restore_kernel_matches_ref(dtype, a, s, c, cs, t0, nch):
    from repro.kernels.kv_restore import ops as kr_ops
    t = nch * cs
    ks = jax.random.split(jax.random.fold_in(RNG, a * s + c + t0), 4)
    # two fields with different channel widths in ONE launch (k/v vs ckv)
    caches = [jax.random.normal(ks[0], (a, s, c), dtype),
              jax.random.normal(ks[1], (a, s, 2 * c), dtype)]
    staged = [jax.random.randint(ks[2], (a, t, c), -127, 128, jnp.int8),
              jax.random.randint(ks[3], (a, t, 2 * c), -127, 128, jnp.int8)]
    scales = [jnp.abs(jax.random.normal(ks[0], (nch, c))) * 0.05 + 1e-3,
              jnp.abs(jax.random.normal(ks[1], (nch, 2 * c))) * 0.05 + 1e-3]
    out_i = kr_ops.kv_restore_scatter(caches, staged, scales, t0=t0,
                                      chunk_size=cs, backend="interpret")
    out_r = kr_ops.kv_restore_scatter(caches, staged, scales, t0=t0,
                                      chunk_size=cs, backend="ref")
    for oi, orr in zip(out_i, out_r):
        np.testing.assert_array_equal(np.asarray(oi), np.asarray(orr))
    # untouched regions preserved bit-exactly despite the aliased in-place
    # partial-grid write
    for cache, oi in zip(caches, out_i):
        np.testing.assert_array_equal(np.asarray(oi)[:, :t0],
                                      np.asarray(cache)[:, :t0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_restore_raw_copy_bit_exact(dtype):
    """quant="none" staging buffers carry the cache dtype: the scatter is
    a pure copy and the restored range equals the payload bit-for-bit."""
    from repro.kernels.kv_restore import ops as kr_ops
    a, s, c, cs, t0, nch = 3, 26, 128, 8, 8, 2
    t = nch * cs
    ks = jax.random.split(jax.random.fold_in(RNG, 11), 2)
    cache = jax.random.normal(ks[0], (a, s, c), dtype)
    staged = jax.random.normal(ks[1], (a, t, c), dtype)
    for backend in ("interpret", "ref"):
        out = kr_ops.kv_restore_scatter([cache], [staged], None, t0=t0,
                                        chunk_size=cs, backend=backend)[0]
        o = np.asarray(out)
        t_eff = min(t, s - t0)
        np.testing.assert_array_equal(o[:, t0:t0 + t_eff],
                                      np.asarray(staged)[:, :t_eff])
        np.testing.assert_array_equal(o[:, :t0], np.asarray(cache)[:, :t0])


def test_kv_restore_slot_subspan():
    """A layer span owning only slots [lo, hi) must leave other slots'
    rows untouched (multi-stage splits restore sub-spans) — in the kernel
    too, whose grid covers the sub-span only."""
    from repro.kernels.kv_restore import ops as kr_ops
    a, s, c, cs = 4, 16, 128, 8
    cache = jax.random.normal(jax.random.fold_in(RNG, 3), (a, s, c))
    staged = jax.random.normal(jax.random.fold_in(RNG, 4), (a, cs, c))
    for backend in ("interpret", "ref"):
        out = kr_ops.kv_restore_scatter([cache], [staged], None, t0=8,
                                        slot_lo=1, n_slots=2, chunk_size=cs,
                                        backend=backend)[0]
        o, ca, st = (np.asarray(x) for x in (out, cache, staged))
        np.testing.assert_array_equal(o[0], ca[0])
        np.testing.assert_array_equal(o[3], ca[3])
        np.testing.assert_array_equal(o[1:3, :8], ca[1:3, :8])
        np.testing.assert_array_equal(o[1:3, 8:16], st[1:3])


def test_kv_restore_dequant_matches_kv_dequantize():
    """The fused scatter's on-device dequant math is bit-identical to the
    storage codec's kv_dequantize — fused restoration lands the same bits
    the legacy decode-then-copy path would."""
    from repro.kernels.kv_quant import ops as kq_ops
    from repro.kernels.kv_restore import ops as kr_ops
    a, s, hk, dh, cs = 2, 16, 2, 64, 8
    x = jax.random.normal(jax.random.fold_in(RNG, 5), (a, 1, cs, hk, dh))
    q, scales = kq_ops.kv_quantize(x, backend="ref")
    dec = kq_ops.kv_dequantize(q, scales, jnp.float32, backend="ref")
    c = hk * dh
    cache = jnp.zeros((a, s, c))
    staged = [jnp.asarray(np.asarray(q).reshape(a, cs, c))]
    sc = [jnp.tile(scales, hk)[None]]          # (1, C): one chunk
    for backend in ("interpret", "ref"):
        out = kr_ops.kv_restore_scatter([cache], staged, sc, t0=0,
                                        chunk_size=cs, backend=backend)[0]
        np.testing.assert_array_equal(
            np.asarray(out)[:, :cs], np.asarray(dec).reshape(a, cs, c))
