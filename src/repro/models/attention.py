"""GQA / MQA / windowed attention with unified full & chunked-cache paths.

Three compute backends:
  * ``naive``  — materialises (S, T) scores; used for short sequences.
  * ``flash``  — pure-jnp online-softmax over key blocks via ``lax.scan``;
                 bounded memory for long sequences (this is also the oracle
                 structure the Pallas kernels implement on TPU).
  * ``pallas`` — ``repro.kernels`` flash kernels (TPU target; interpret mode
                 on CPU for tests).

The chunked path (``apply_attention_chunk``) is the restoration primitive:
queries of a chunk attend to [cached prefix || chunk] and the chunk's KV is
written into the cache — one recompute-pointer step of CacheFlow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_head_norm

NEG_INF = -1e30
_FLASH_THRESHOLD = 8192       # use blocked attention above this many keys
_FLASH_BLOCK = 1024


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, hq, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    # flattened head dims => always divisible by the "model" mesh axis
    p = {
        "wq": dense_init(ks[0], (d, hq * dh), dtype),
        "wk": dense_init(ks[1], (d, hk * dh), dtype),
        "wv": dense_init(ks[2], (d, hk * dh), dtype),
        "wo": dense_init(ks[3], (hq * dh, d), dtype),
    }
    if cfg.use_qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hk * dh,), dtype)
        p["bv"] = jnp.zeros((hk * dh,), dtype)
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _project_qkv(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) -> q (B,S,Hq,Dh), k/v (B,S,Hk,Dh), rope applied."""
    b, s, _ = x.shape
    hq, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.use_qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hk, dh)
    v = v.reshape(b, s, hk, dh)
    if cfg.use_qk_norm:
        q = rms_head_norm(q, params["q_norm"])
        k = rms_head_norm(k, params["k_norm"])
    if cfg.position == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


# ---------------------------------------------------------------------------
# Core attention math (grouped heads)
# ---------------------------------------------------------------------------


def _gqa_scores_naive(q, k, v, mask, scale):
    """q:(B,S,Hq,Dh) k/v:(B,T,Hk,Dh) mask:(B,S,T) or (S,T) -> (B,S,Hq,Dh)."""
    b, s, hq, dh = q.shape
    hk = k.shape[2]
    g = hq // hk
    qg = q.reshape(b, s, hk, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hq, dh)


def _gqa_flash(q, k, v, q_pos, k_pos, scale, window: int, block: int = _FLASH_BLOCK):
    """Online-softmax attention, scanning key blocks; O(S·block) memory.

    q:(B,S,Hq,Dh); k/v:(B,T,Hk,Dh); q_pos:(B,S) int32; k_pos:(B,T) int32
    (entries < 0 are invalid/empty cache slots).
    """
    from repro.distributed.constraints import _ambient_mesh, constrain
    # Distribution of the blocked attention: shard heads over "model" when
    # they divide the axis; otherwise fall back to SEQUENCE-parallel queries
    # (q rows are independent in flash attention) with replicated KV — this
    # is what keeps 24-head/8-kv archs (phi4) from replicating the whole
    # attention computation per shard.
    mesh = _ambient_mesh()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    if msize > 1 and q.shape[2] % msize == 0:
        q = constrain(q, ("pod", "data"), None, "model", None)
        k = constrain(k, ("pod", "data"), None, "model", None)
        v = constrain(v, ("pod", "data"), None, "model", None)
    elif msize > 1 and q.shape[1] > 1 and q.shape[1] % msize == 0:
        q = constrain(q, ("pod", "data"), "model", None, None)
        k = constrain(k, ("pod", "data"), None, None, None)
        v = constrain(v, ("pod", "data"), None, None, None)
    b, s, hq, dh = q.shape
    t = k.shape[1]
    hk = k.shape[2]
    g = hq // hk
    dv = v.shape[-1]          # may differ from dh (MLA: qk 192, v 128)
    if t % block:
        pad = block - t % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        t += pad
    nb = t // block
    qg = q.reshape(b, s, hk, g, dh)
    kb = k.reshape(b, nb, block, hk, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block, hk, dv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(b, nb, block).transpose(1, 0, 2)

    def step(carry, blk):
        m, l, acc = carry                      # (B,Hk,G,S), (B,Hk,G,S), (B,S,Hk,G,Dh)
        kc, vc, pc = blk
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, kc).astype(jnp.float32) * scale
        valid = (pc[:, None, :] <= q_pos[:, :, None]) & (pc[:, None, :] >= 0)
        if window > 0:
            valid &= pc[:, None, :] > q_pos[:, :, None] - window
        sc = jnp.where(valid[:, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype), vc).astype(jnp.float32)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hk, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g, s), jnp.float32)
    a0 = jnp.zeros((b, s, hk, g, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s, hq, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full-sequence path (train / prefill)
# ---------------------------------------------------------------------------


def attention_full(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
                   backend: str = "auto"):
    """Causal self-attention over the whole sequence.

    Returns (out (B,S,D), (k, v)) — callers keep k/v when building a cache.
    """
    q, k, v = _project_qkv(cfg, params, x, positions)
    scale = 1.0 / (cfg.qk_head_dim ** 0.5)
    b, s = x.shape[:2]
    use_flash = backend == "flash" or (backend in ("auto", "pallas") and s > _FLASH_THRESHOLD)
    if backend == "pallas" and s <= 0:
        pass  # pallas dispatch happens in repro.kernels.dispatch (model-level flag)
    if use_flash:
        out = _gqa_flash(q, k, v, positions, positions, scale, cfg.attn_window)
    else:
        i = positions[:, :, None] if positions.ndim == 2 else positions[:, None]
        j = positions[:, None, :] if positions.ndim == 2 else positions[None, :]
        mask = j <= i
        if cfg.attn_window:
            mask &= j > i - cfg.attn_window
        if mask.ndim == 2:
            mask = mask[None]
        mask = jnp.broadcast_to(mask, (b, s, s))
        out = _gqa_scores_naive(q, k, v, mask, scale)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"].astype(x.dtype), (k, v)


# ---------------------------------------------------------------------------
# Chunked path (restoration recompute step / decode)
# ---------------------------------------------------------------------------


def attention_chunk(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
                    k_cache: jax.Array, v_cache: jax.Array, kpos: jax.Array,
                    backend: str = "auto"):
    """Chunk queries attend to [cache || chunk]; chunk KV is written back.

    x: (B, C, D) chunk activations; positions: (B, C) absolute positions.
    k_cache/v_cache: (B, S_cache, Hk, Dh); kpos: (S_cache,) slot positions.
    Returns (out, k_cache', v_cache', kpos').
    """
    b, c, _ = x.shape
    s_cache = k_cache.shape[1]
    q, k, v = _project_qkv(cfg, params, x, positions)
    # --- write chunk KV into the cache (ring buffer if windowed) ---
    slot = positions[0] % s_cache if cfg.attn_window else positions[0]
    k_cache = k_cache.at[:, slot].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[:, slot].set(v.astype(v_cache.dtype))
    kpos = kpos.at[slot].set(positions[0])
    scale = 1.0 / (cfg.qk_head_dim ** 0.5)
    kp = jnp.broadcast_to(kpos[None], (b, s_cache))
    if c == 1 or s_cache > _FLASH_THRESHOLD or backend == "flash":
        out = _gqa_flash(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                         positions, kp, scale, cfg.attn_window,
                         block=min(_FLASH_BLOCK, max(128, s_cache)))
    else:
        mask = (kp[:, None, :] <= positions[:, :, None]) & (kp[:, None, :] >= 0)
        if cfg.attn_window:
            mask &= kp[:, None, :] > positions[:, :, None] - cfg.attn_window
        out = _gqa_scores_naive(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                                mask, scale)
    out = out.reshape(b, c, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"].astype(x.dtype), k_cache, v_cache, kpos
