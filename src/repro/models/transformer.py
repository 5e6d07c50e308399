"""Per-layer blocks (pre-norm residual) shared by the stack in ``model.py``.

Every block comes in two entry points:
  * ``*_full``  — whole-sequence forward (train / prefill); emits the state
                  the cache stores for that layer kind.
  * ``*_cached``— chunk forward against an existing cache (restoration
                  recompute steps and single-token decode are the same path
                  with C = chunk or C = 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, layer_idx: int, dtype) -> dict:
    kind = cfg.layer_kinds()[layer_idx]
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "norm1": init_norm(cfg.norm, cfg.d_model, dtype),
        "norm2": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if kind == "attention":
        p["attn"] = (mla_mod.init_mla(k1, cfg, dtype) if cfg.mla is not None
                     else attn.init_attention(k1, cfg, dtype))
    elif kind == "recurrent":
        p["rglru"] = rglru_mod.init_rglru_block(k1, cfg, dtype)
    elif kind == "rwkv":
        p["rwkv"] = rwkv_mod.init_rwkv_block(k1, cfg, dtype)
        return p  # rwkv blocks have no separate MLP (channel mix is inside)
    # FFN: dense or MoE
    if cfg.moe is not None and layer_idx >= cfg.moe.first_k_dense:
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    else:
        d_ff = (cfg.moe.dense_d_ff if (cfg.moe is not None and cfg.moe.dense_d_ff)
                else cfg.d_ff)
        p["mlp"] = init_mlp(k2, cfg.d_model, d_ff, cfg.activation, dtype)
    return p


# ---------------------------------------------------------------------------
# Full-sequence blocks
# ---------------------------------------------------------------------------


def _ffn(cfg: ModelConfig, p: dict, h: jax.Array, moe_groups: int):
    if "moe" in p:
        y, aux = moe_mod.apply_moe(p["moe"], h, cfg, num_groups=moe_groups)
        return y, aux
    return apply_mlp(p["mlp"], h, cfg.activation), jnp.zeros((), jnp.float32)


def attention_layer_full(cfg: ModelConfig, p: dict, x, positions, *, backend="auto",
                         moe_groups: int = 0):
    """Returns (x', layer_cache_entry, aux). Cache entry:
    {"k","v"} or {"ckv"} for the *whole* sequence."""
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, ckv = mla_mod.mla_full(cfg, p["attn"], h, positions, backend)
        entry = {"ckv": ckv}
    else:
        a, (k, v) = attn.attention_full(cfg, p["attn"], h, positions, backend)
        entry = {"k": k, "v": v}
    x = x + a
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    f, aux = _ffn(cfg, p, h, moe_groups)
    return x + f, entry, aux


def recurrent_layer_full(cfg: ModelConfig, p: dict, x, conv_tail, h0, *, backend="auto"):
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    r, conv_tail, h_last = rglru_mod.rglru_full(cfg, p["rglru"], h, conv_tail, h0, backend)
    x = x + r
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    f, _ = _ffn(cfg, p, h, 0)
    return x + f, conv_tail, h_last


def rwkv_layer_full(cfg: ModelConfig, p: dict, x, shift_tm, shift_cm, wkv, *, backend="auto"):
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    t, shift_tm, wkv = rwkv_mod.time_mix(cfg, p["rwkv"], h, shift_tm, wkv, backend)
    x = x + t
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    c, shift_cm = rwkv_mod.channel_mix(cfg, p["rwkv"], h, shift_cm)
    return x + c, shift_tm, shift_cm, wkv


# ---------------------------------------------------------------------------
# Cached-chunk blocks (restoration recompute / decode)
# ---------------------------------------------------------------------------


def attention_layer_cached(cfg: ModelConfig, p: dict, x, positions, layer_cache: dict,
                           *, backend="auto", moe_groups: int = 0):
    """layer_cache: {"k","v","kpos"} or {"ckv","kpos"} views for THIS layer.
    Returns (x', updated layer_cache)."""
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, ckv, kpos = mla_mod.mla_chunk(cfg, p["attn"], h, positions,
                                         layer_cache["ckv"], layer_cache["kpos"], backend)
        new_cache = {"ckv": ckv, "kpos": kpos}
    else:
        a, k, v, kpos = attn.attention_chunk(cfg, p["attn"], h, positions,
                                             layer_cache["k"], layer_cache["v"],
                                             layer_cache["kpos"], backend)
        new_cache = {"k": k, "v": v, "kpos": kpos}
    x = x + a
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    f, _ = _ffn(cfg, p, h, moe_groups)
    return x + f, new_cache


def latent_layer_decode(cfg: ModelConfig, p: dict, x, positions, lat, kpos,
                        *, moe_groups: int = 0):
    """One decode token of an MLA layer against its latent cache ``lat``
    (B,S,d_c) and ``kpos`` (S,), both read only.  Returns (x', the token's
    latent (B,1,d_c)), which the caller writes at the token's slot."""
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    a, new = mla_mod.mla_decode(cfg, p["attn"], h, positions, lat, kpos)
    x = x + a
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    f, _ = _ffn(cfg, p, h, moe_groups)
    return x + f, new
