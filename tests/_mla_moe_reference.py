"""A plain float32 reference of the DeepSeek-V2 block, for the CPU tests.

Written from the published equations (arXiv:2405.04434 §2.1-2.2 and the
DeepSeek-V2 modelling code) with ``jax.numpy`` alone: nothing of
``repro.models`` is imported.  It reads the program's parameter tree and
the sizes of its ``ModelConfig``, and walks the whole sequence at once:

* MLA with K and V decompressed per head (not absorbed):
  ``q = h Wq``; ``c ‖ k_pe = h Wkv_a``, ``c = RMSNorm(c)``;
  ``k_nope ‖ v = c Wkv_b``; RoPE on ``q_pe`` and the shared ``k_pe``;
  ``softmax([q_nope‖q_pe]·[k_nope‖k_pe]ᵀ · s)`` causal, ``s = d_qk^-½``
  times YaRN's ``mscale²`` where the config scales RoPE;
* YaRN RoPE (:func:`yarn_inv_freq`, :func:`yarn_mscale`), rotate-half;
* the expert layer: a float32 softmax router over every expert, the greedy
  top-k, renormalised only where ``norm_topk_prob``; the experts this
  chip holds weighted by it; the shared experts added once.  Routes to experts held elsewhere add
  nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def yarn_mscale(factor: float, m: float) -> float:
    """``0.1·m·ln(factor) + 1``, or 1 where the factor does not stretch."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_bounds(d: int, theta: float, original_max: int, beta_fast: float,
                beta_slow: float):
    """``(low, high)``: the rotary pairs below ``low`` keep their
    frequency, those from ``high`` on are stretched by the factor."""
    def c(r):
        return d * math.log(original_max / (2 * math.pi * r)) \
            / (2 * math.log(theta))
    return (max(math.floor(c(beta_fast)), 0),
            min(math.ceil(c(beta_slow)), d - 1))


def yarn_inv_freq(d: int, theta: float, y) -> np.ndarray:
    """Inverse frequencies (float64) of a head of ``d`` rotary dims; ``y``
    a YaRN setting or None for the plain ones."""
    extra = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    if y is None:
        return extra
    low, high = yarn_bounds(d, theta, y.original_max_position_embeddings,
                            y.beta_fast, y.beta_slow)
    m = 1.0 - np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                      0.0, 1.0)
    return extra / y.factor * (1.0 - m) + extra * m


def softmax_scale(cfg) -> float:
    m = cfg.mla
    s = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def rope(x, pos, cfg):
    """Rotate-half RoPE of x (S, H, d) at positions pos (S,)."""
    d = x.shape[-1]
    y = cfg.rope_scaling
    inv = jnp.asarray(yarn_inv_freq(d, cfg.rope_theta, y), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    g = 1.0 if y is None else (yarn_mscale(y.factor, y.mscale)
                               / yarn_mscale(y.factor, y.mscale_all_dim))
    c, s = jnp.cos(ang) * g, jnp.sin(ang) * g
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def attention(p, h, cfg):
    """MLA over the whole sequence h (S, D), K and V decompressed."""
    m, hn = cfg.mla, cfg.num_heads
    s = h.shape[0]
    pos = jnp.arange(s)
    q = mm(h, p["wq"]).reshape(s, hn, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q = jnp.concatenate([q[..., :m.qk_nope_head_dim],
                         rope(q[..., m.qk_nope_head_dim:], pos, cfg)], -1)
    ckv = mm(h, p["wkv_a"])
    c = rms(ckv[:, :m.kv_lora_rank], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope(ckv[:, None, m.kv_lora_rank:], pos, cfg)
    kv = mm(c, p["wkv_b"]).reshape(s, hn, m.qk_nope_head_dim + m.v_head_dim)
    k = jnp.concatenate([kv[..., :m.qk_nope_head_dim],
                         jnp.broadcast_to(k_pe, (s, hn, m.qk_rope_head_dim))],
                        -1)
    v = kv[..., m.qk_nope_head_dim:]
    sc = jnp.einsum("qhd,thd->hqt", q, k, precision=HI) * softmax_scale(cfg)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = jnp.einsum("hqt,thd->qhd", jax.nn.softmax(sc, -1), v, precision=HI)
    return mm(o.reshape(s, hn * m.v_head_dim), p["wo"])


def ffn(p, h):
    return mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
              p["w_down"])


def router_weights(p, h, moe):
    """(S, E_held) weight of each held expert for each token."""
    probs = jax.nn.softmax(mm(h, p["router"]), -1)
    top_p, top_i = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    held = moe.expert_offset + jnp.arange(moe.experts_held or moe.num_experts)
    return jnp.sum(top_p[:, :, None] * (top_i[:, :, None] == held), axis=1)


def routed(p, h, moe):
    """What the held experts add: Σ_e r_e(h) · FFN_e(h)."""
    r = router_weights(p, h, moe)
    g = jax.nn.silu(jnp.einsum("sd,edf->sef", h, p["w_gate"], precision=HI))
    u = jnp.einsum("sd,edf->sef", h, p["w_up"], precision=HI)
    y = jnp.einsum("sef,efd->sed", g * u, p["w_down"], precision=HI)
    return jnp.einsum("se,sed->sd", r, y, precision=HI)


def layer(p, x, cfg):
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
    x = x + attention(f32["attn"], rms(x, f32["norm1"]["scale"],
                                       cfg.norm_eps), cfg)
    h = rms(x, f32["norm2"]["scale"], cfg.norm_eps)
    if "moe" in f32:
        return x + routed(f32["moe"], h, cfg.moe) + ffn(f32["moe"]["shared"],
                                                        h)
    return x + ffn(f32["mlp"], h)


def forward(params, tokens, cfg):
    """Logits (S, V) of the token ids (S,) over the whole stack."""
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    for p in params["prefix_layers"]:
        x = layer(p, x, cfg)
    stack = params["scan_layers"]
    for i in range(cfg.num_layers - len(params["prefix_layers"])):
        x = layer(jax.tree.map(lambda a: a[i], stack), x, cfg)
    x = rms(x, jnp.asarray(params["final_norm"]["scale"], jnp.float32),
            cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return mm(x, jnp.asarray(head, jnp.float32))
