"""Common building blocks: norms, MLPs, rotary / sinusoidal positions.

All modules are functional: ``init_*`` returns a param pytree (nested dict),
the apply function takes ``(params, x, ...)``.  Parameter leaves are created
in ``param_dtype``; compute runs in the dtype of the incoming activations
(callers cast at the model boundary).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0):
    """Truncated-normal fan-in init (matches common LLM inits closely enough
    for loss-goes-down purposes)."""
    fan_in = shape[in_axis] if isinstance(in_axis, int) else int(np.prod([shape[a] for a in in_axis]))
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(kind: str, dim: int, dtype) -> dict:
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(kind: str, params: dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    elif kind == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        return (y * params["scale"].astype(jnp.float32)
                + params["bias"].astype(jnp.float32)).astype(x.dtype)
    raise ValueError(kind)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (Qwen3 style): normalise the trailing head_dim."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated and plain)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, (d_model, d_ff), dtype),
            "w_up": dense_init(k2, (d_model, d_ff), dtype),
            "w_down": dense_init(k3, (d_ff, d_model), dtype),
        }
    return {
        "w_up": dense_init(k1, (d_model, d_ff), dtype),
        "w_down": dense_init(k2, (d_ff, d_model), dtype),
    }


def apply_mlp(params: dict, x: jax.Array, activation: str) -> jax.Array:
    if activation in ("swiglu", "geglu"):
        act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
        g = act(x @ params["w_gate"].astype(x.dtype))
        u = x @ params["w_up"].astype(x.dtype)
        return (g * u) @ params["w_down"].astype(x.dtype)
    h = jax.nn.gelu(x @ params["w_up"].astype(x.dtype))
    return h @ params["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """Inverse frequencies of the rotary pairs; with a
    :class:`~repro.config.YarnConfig`, DeepSeek's YaRN blend of the plain
    (extrapolated) and the ``factor``-stretched (interpolated) ones."""
    if yarn is None:
        return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    inter = extra / yarn.factor

    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1: plain frequency, 0: stretched
    return jnp.asarray(inter * (1.0 - keep) + extra * keep, jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention magnitude factor ``0.1·m·ln(s) + 1`` (1 for s ≤ 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, Dh) or (..., S, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta, yarn)                        # (Dh/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, Dh/2)
    if x.ndim == angles.ndim + 1:                              # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """(..., S) int positions -> (..., S, d_model) sinusoidal embedding."""
    half = d_model // 2
    freqs = jnp.exp(-np.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap
