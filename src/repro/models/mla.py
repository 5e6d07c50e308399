"""DeepSeek-V2 Multi-head Latent Attention (MLA).

The KV cache stores only the compressed latent ``c_kv`` (kv_lora_rank) plus a
shared rotary key (qk_rope_head_dim) per token — 592 dims/layer for V2 vs
~32k for an equivalent MHA cache row.  For CacheFlow this shrinks T_io per
token ~55×, pushing the token-wise crossover L_Δ strongly toward
recomputation (see DESIGN.md §5).

Two attention paths:
  * ``mla_full``  — prefill/train: decompress per-head K/V and run flash
    (blocked online-softmax) attention for long sequences.
  * ``mla_chunk`` — decode/restoration chunks: **absorbed** attention — scores
    and values are computed directly against the compressed latents
    (q̃ = q·W_uk, out = probs·c_kv·W_uv), never materialising per-head K/V of
    the whole cache.  This is the TPU-friendly analogue of DeepSeek's decode
    kernel and is what makes decode_32k/B=128 memory-feasible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.attention import _gqa_flash, _FLASH_THRESHOLD
from repro.models.layers import (apply_norm, apply_rope, dense_init, init_norm,
                                 yarn_mscale)

NEG_INF = -1e30


def softmax_scale(cfg: ModelConfig) -> float:
    """1/√d_qk, times YaRN's ``mscale²`` where the config scales RoPE with
    a nonzero ``mscale_all_dim`` (DeepSeek-V2)."""
    scale = 1.0 / (cfg.qk_head_dim ** 0.5)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if m.q_lora_rank > 0:
        p["wq_a"] = dense_init(ks[0], (d, m.q_lora_rank), dtype)
        p["q_norm"] = init_norm("rmsnorm", m.q_lora_rank, dtype)
        p["wq_b"] = dense_init(ks[1], (m.q_lora_rank, h * qk_dim), dtype)
    else:
        p["wq"] = dense_init(ks[0], (d, h * qk_dim), dtype)
    p["wkv_a"] = dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype)
    p["kv_norm"] = init_norm("rmsnorm", m.kv_lora_rank, dtype)
    p["wkv_b"] = dense_init(ks[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)), dtype)
    p["wo"] = dense_init(ks[4], (h * m.v_head_dim, d), dtype)
    return p


def _project_q(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank > 0:
        ql = x @ params["wq_a"].astype(x.dtype)
        ql = apply_norm("rmsnorm", params["q_norm"], ql, cfg.norm_eps)
        q = ql @ params["wq_b"].astype(x.dtype)
    else:
        q = x @ params["wq"].astype(x.dtype)
    q = q.reshape(b, s, h, qk_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def compress_kv(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array):
    """x -> [c_kv (normalised) || k_rope (rotated)]: (B,S,lora+rope).
    This is exactly what the cache stores and what restoration I/O moves."""
    m = cfg.mla
    kv = x @ params["wkv_a"].astype(x.dtype)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = apply_norm("rmsnorm", params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def _uk_uv(cfg: ModelConfig, params: dict, dtype):
    m = cfg.mla
    h = cfg.num_heads
    wkv_b = params["wkv_b"].astype(dtype).reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., : m.qk_nope_head_dim]   # (lora, H, nope)
    w_uv = wkv_b[..., m.qk_nope_head_dim:]    # (lora, H, vd)
    return w_uk, w_uv


def mla_full(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
             backend: str = "auto"):
    """Full causal MLA (prefill/train). Returns (out, ckv latent for caching)."""
    with jax.named_scope("mla"):
        return _mla_full(cfg, params, x, positions, backend)


def _mla_full(cfg, params, x, positions, backend):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _project_q(cfg, params, x, positions)
    ckv = compress_kv(cfg, params, x, positions)
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    # decompress per-head K/V (sharded over heads on the mesh; fine for prefill)
    kv = c_kv @ params["wkv_b"].astype(x.dtype)
    kv = kv.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, m.qk_rope_head_dim))],
                        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = softmax_scale(cfg)
    if s > _FLASH_THRESHOLD or backend == "flash":
        # pad v to qk dim? no — flash handles differing v dim via separate arg shapes
        out = _gqa_flash(q, k, v, positions, positions, scale, 0)
    else:
        sc = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
        mask = positions[:, :, None] >= positions[:, None, :]
        sc = jnp.where(mask[:, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhst,bthd->bshd", p, v)
    out = out.reshape(b, s, h * m.v_head_dim)
    return out @ params["wo"].astype(x.dtype), ckv


def mla_chunk(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
              ckv_cache: jax.Array, kpos: jax.Array, backend: str = "auto"):
    """Absorbed-matrix chunk/decode attention over the latent cache.

    x: (B,C,D); ckv_cache: (B,S_cache,lora+rope); kpos: (S_cache,).
    Returns (out, ckv_cache', kpos').
    """
    with jax.named_scope("mla"):
        return _mla_chunk(cfg, params, x, positions, ckv_cache, kpos)


def _mla_chunk(cfg, params, x, positions, ckv_cache, kpos):
    q_nope, q_rope = _project_q(cfg, params, x, positions)
    ckv = compress_kv(cfg, params, x, positions)
    slot = positions[0]
    ckv_cache = ckv_cache.at[:, slot].set(ckv.astype(ckv_cache.dtype))
    kpos = kpos.at[slot].set(positions[0])
    out = mla_attend_absorbed(cfg, params, q_nope, q_rope, positions,
                              ckv_cache.astype(x.dtype), kpos)
    return out, ckv_cache, kpos


def mla_decode(cfg: ModelConfig, params: dict, x: jax.Array,
               positions: jax.Array, lat: jax.Array, kpos: jax.Array):
    """One new token's absorbed attention over a latent cache it does not
    write: the cached tokens before it (``kpos`` below its position) and
    the token's own latent, under one softmax.  Scores and values each
    read the cache once, over all ``lora+rope`` channels, so no part of
    it is copied.

    x: (B,1,D); lat: (B,S,lora+rope); kpos: (S,).
    Returns (out, the token's latent (B,1,lora+rope) in ``lat``'s dtype)."""
    with jax.named_scope("mla"):
        return _mla_decode(cfg, params, x, positions, lat, kpos)


def _mla_decode(cfg, params, x, positions, lat, kpos):
    m = cfg.mla
    b, c = x.shape[:2]
    h = cfg.num_heads
    dt = x.dtype
    q_nope, q_rope = _project_q(cfg, params, x, positions)
    new = compress_kv(cfg, params, x, positions).astype(lat.dtype)
    w_uk, w_uv = _uk_uv(cfg, params, dt)
    q_lat = jnp.einsum("bchd,lhd->bchl", q_nope, w_uk)
    # [q̃ || q_rope] against [c_kv || k_rope]: one contraction per read
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)
    lat, cur = lat.astype(dt), new.astype(dt)
    scale = softmax_scale(cfg)
    sc = jnp.einsum("bchl,btl->bhct", q, lat,
                    preferred_element_type=jnp.float32) * scale
    kp = kpos[None, None, None, :]
    sc = jnp.where((kp < positions[:, None, :, None]) & (kp >= 0), sc, NEG_INF)
    sc_cur = jnp.einsum("bchl,bnl->bhcn", q, cur,
                        preferred_element_type=jnp.float32) * scale
    top = jnp.maximum(sc.max(-1, keepdims=True), sc_cur)
    e, e_cur = jnp.exp(sc - top), jnp.exp(sc_cur - top)
    den = e.sum(-1, keepdims=True) + e_cur
    o = (jnp.einsum("bhct,btl->bchl", (e / den).astype(dt), lat)
         + jnp.einsum("bhcn,bnl->bchl", (e_cur / den).astype(dt), cur))
    out = jnp.einsum("bchl,lhd->bchd", o[..., :m.kv_lora_rank], w_uv)
    out = out.reshape(b, c, h * m.v_head_dim)
    return out @ params["wo"].astype(dt), new


def mla_attend_absorbed(cfg: ModelConfig, params: dict, q_nope, q_rope,
                        positions, lat, kpos):
    """Absorbed attention over a (read-only) latent cache view."""
    m = cfg.mla
    b, c = q_nope.shape[:2]
    h = cfg.num_heads
    x_dtype = q_nope.dtype
    c_kv, k_rope = jnp.split(lat, [m.kv_lora_rank], axis=-1)     # (B,T,lora),(B,T,rope)
    w_uk, w_uv = _uk_uv(cfg, params, x_dtype)
    # absorb W_uk into q: q̃ (B,C,H,lora)
    q_lat = jnp.einsum("bchd,lhd->bchl", q_nope, w_uk)
    scale = softmax_scale(cfg)
    sc = jnp.einsum("bchl,btl->bhct", q_lat, c_kv)
    sc += jnp.einsum("bchd,btd->bhct", q_rope, k_rope)
    sc = sc.astype(jnp.float32) * scale
    kp = kpos[None, None, None, :]
    mask = (kp <= positions[:, None, :, None]) & (kp >= 0)
    sc = jnp.where(mask, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1).astype(x_dtype)
    # out in latent space, then absorb W_uv
    o_lat = jnp.einsum("bhct,btl->bchl", p, c_kv)
    out = jnp.einsum("bchl,lhd->bchd", o_lat, w_uv)
    out = out.reshape(b, c, h * m.v_head_dim)
    return out @ params["wo"].astype(x_dtype)
