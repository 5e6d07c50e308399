"""Mixture-of-Experts FFN (DeepSeek-style shared + routed, top-k).

TPU-native dispatch (GShard lineage, scatter formulation): tokens are
scattered into a per-expert capacity buffer ``(E, C, D)``, expert FFNs run as
dense einsums over that buffer, results are gathered back and combined with
router weights.  The buffer's expert axis is sharded over the "model" mesh
axis (expert parallelism) and its capacity axis over "data" — GSPMD derives
the token all-to-all from the shardings.

Memory is bounded by ``num_groups``: tokens are processed in sequential
groups via ``lax.scan``, capping the dispatch buffers at
``tokens/num_groups × top_k`` slots (the classic GShard group trick).

Expert share: a chip of an expert-parallel deployment may hold only a
contiguous range of the routed experts (``MoEConfig.expert_offset``,
``experts_held``).  The router still scores every expert and picks the
top-k over all of them; the layer returns the part of the result that the
held experts give, plus the shared experts.  A route to an expert held
elsewhere adds nothing here: the shares of all chips sum to the whole
layer (no exchange between chips is modelled).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MoEConfig
from repro.models.layers import apply_mlp, dense_init, init_mlp


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.moe
    d = cfg.d_model
    k_router, k_exp, k_shared = jax.random.split(key, 3)
    gated = cfg.activation in ("swiglu", "geglu")
    n_mats = 3 if gated else 2
    ek = jax.random.split(k_exp, n_mats)
    e = m.n_held      # the router scores every expert; only held ones have weights
    p = {
        "router": dense_init(k_router, (d, m.num_experts), jnp.float32),
        # stacked expert weights: (E_held, d, f) / (E_held, f, d)
        "w_gate": dense_init(ek[0], (e, d, m.expert_d_ff), dtype, in_axis=1),
        "w_up": dense_init(ek[1 % n_mats], (e, d, m.expert_d_ff), dtype, in_axis=1),
        "w_down": dense_init(ek[-1], (e, m.expert_d_ff, d), dtype, in_axis=1),
    }
    if not gated:
        del p["w_gate"]
    shared_ff = m.shared_d_ff or m.num_shared_experts * m.expert_d_ff
    if shared_ff:
        p["shared"] = init_mlp(k_shared, d, shared_ff, cfg.activation, dtype)
    return p


def _expert_ffn(params: dict, xb: jax.Array, activation: str) -> jax.Array:
    """xb: (E, C, D) -> (E, C, D) through per-expert weights."""
    if activation in ("swiglu", "geglu"):
        act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
        g = act(jnp.einsum("ecd,edf->ecf", xb, params["w_gate"].astype(xb.dtype)))
        u = jnp.einsum("ecd,edf->ecf", xb, params["w_up"].astype(xb.dtype))
        h = g * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xb, params["w_up"].astype(xb.dtype)))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(xb.dtype))


# dispatch mechanism: "scatter" (token scatter/gather; GSPMD lowers the
# cross-shard scatter to full-buffer all-reduces — collective-heavy) or
# "einsum" (GShard one-hot dispatch matmuls; partitions into one all-to-all,
# at the cost of T·E·C·D dispatch FLOPs). See EXPERIMENTS.md §Perf.
DISPATCH = "scatter"


def route(params: dict, x: jax.Array, moe: MoEConfig):
    """Router over every expert, held or not: softmax in float32, greedy
    top-k, renormalised where ``norm_topk_prob``.
    x: (T, D) -> (probs (T, E), top_p (T, k), top_i (T, k) global ids)."""
    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, moe.top_k)                            # (T, k)
    if moe.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_i


def moe_group(params: dict, x: jax.Array, moe: MoEConfig, activation: str):
    """One group of tokens through the routed experts.

    x: (T, D) -> (y (T, D), aux_loss scalar)
    """
    t, d = x.shape
    e, k = moe.num_experts, moe.top_k
    # capacity_factor <= 0 => dropless (cap = t covers the worst case: every
    # token hits the same expert once). Serving/restoration MUST be dropless
    # so chunked recomputation reproduces the full-prefill KV bit-for-bit.
    cap = t if moe.capacity_factor <= 0 else max(1, int(t * k / e * moe.capacity_factor))
    if DISPATCH == "einsum" and moe.capacity_factor > 0:
        return _moe_group_einsum(params, x, moe, activation, cap)

    from repro.distributed.constraints import constrain

    probs, top_p, top_i = route(params, x, moe)

    # --- slot assignment: position of each (token, k) among its expert's
    # hits, over the held experts (a route to an expert held elsewhere has
    # no slot and adds nothing here)
    flat_e = top_i.reshape(t * k)                                             # (T·k,)
    held = moe.n_held
    local = flat_e - moe.expert_offset if moe.expert_offset else flat_e       # held index
    onehot = jax.nn.one_hot(local, held, dtype=jnp.int32)                     # (T·k, E_held)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    is_held = None
    if held != e:
        is_held = (local >= 0) & (local < held)
        local = jnp.clip(local, 0, held - 1)
    slot = jnp.take_along_axis(pos_in_e, local[:, None], axis=1)[:, 0]        # (T·k,)
    keep = slot < cap
    if is_held is not None:
        keep = keep & is_held

    # --- scatter tokens into the (E, C, D) buffer; EP: experts over "model",
    # capacity over "data" — GSPMD derives the token all-to-all
    x_rep = jnp.repeat(x, k, axis=0)                                          # (T·k, D)
    x_rep = jnp.where(keep[:, None], x_rep, 0)
    x_rep = constrain(x_rep, ("pod", "data"), None)
    buf = jnp.zeros((held, cap, d), x.dtype)
    buf = buf.at[local, jnp.minimum(slot, cap - 1)].add(x_rep)
    buf = constrain(buf, "model", "data", None)

    y_buf = _expert_ffn(params, buf, activation)                              # (E, C, D)
    y_buf = constrain(y_buf, "model", "data", None)

    # --- gather back + combine
    y_rep = y_buf[local, jnp.minimum(slot, cap - 1)]                          # (T·k, D)
    y_rep = jnp.where(keep[:, None], y_rep, 0)
    w = (top_p.reshape(t * k, 1)).astype(y_rep.dtype)
    y = (y_rep * w).reshape(t, k, d).sum(axis=1)

    # --- load-balancing aux loss (Switch style)
    me = probs.mean(axis=0)                                                   # (E,)
    ce = jnp.bincount(flat_e, length=e).astype(jnp.float32) / (t * k)
    aux = e * jnp.sum(me * ce) * moe.router_aux_loss
    return y, aux


def _moe_group_einsum(params: dict, x: jax.Array, moe: MoEConfig,
                      activation: str, cap: int):
    """GShard-style one-hot dispatch: one all-to-all instead of scatter
    all-reduces. Keep groups small (T ≈ 2-4k) so the (T,E,C) one-hot fits."""
    from repro.distributed.constraints import constrain
    t, d = x.shape
    e, k = moe.num_experts, moe.top_k
    probs, top_p, top_i = route(params, x, moe)
    held = moe.n_held
    # one-hot over the held experts: a route elsewhere is all zeros
    onehot_e = jax.nn.one_hot(top_i - moe.expert_offset, held,
                              dtype=jnp.float32)                     # (T,k,E_held)
    # position of each (t,k) hit within its expert
    pos = jnp.cumsum(onehot_e.reshape(t * k, held), axis=0) - onehot_e.reshape(t * k, held)
    slot = (pos.reshape(t, k, held) * onehot_e).sum(-1).astype(jnp.int32)  # (T,k)
    keep = slot < cap
    onehot_c = jax.nn.one_hot(slot, cap, dtype=x.dtype) * keep[..., None]
    # dispatch (T,E,C) = Σ_k onehot_e ⊗ onehot_c
    disp = jnp.einsum("tke,tkc->tec", onehot_e.astype(x.dtype), onehot_c)
    disp = constrain(disp, ("pod", "data"), "model", None)
    comb = jnp.einsum("tke,tkc,tk->tec", onehot_e.astype(x.dtype), onehot_c,
                      top_p.astype(x.dtype))
    comb = constrain(comb, ("pod", "data"), "model", None)
    buf = jnp.einsum("tec,td->ecd", disp, x)
    buf = constrain(buf, "model", "data", None)
    y_buf = _expert_ffn(params, buf, activation)
    y_buf = constrain(y_buf, "model", "data", None)
    y = jnp.einsum("tec,ecd->td", comb, y_buf)

    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(axis=(0, 1)) / (t * k)
    aux = e * jnp.sum(me * ce) * moe.router_aux_loss
    return y, aux


def apply_moe(params: dict, x: jax.Array, cfg: ModelConfig, num_groups: int = 0):
    """x: (B, S, D) -> (y, aux_loss). ``num_groups`` > 1 bounds dispatch
    memory by scanning groups of the SEQUENCE axis sequentially (grouping
    along S keeps the batch-axis sharding intact — grouping along B would
    force a gather whenever groups < batch shards)."""
    with jax.named_scope("moe"):
        return _apply_moe(params, x, cfg, num_groups)


def _apply_moe(params, x, cfg, num_groups):
    m = cfg.moe
    b, s, d = x.shape
    if num_groups <= 1 or s % num_groups:
        y, aux = moe_group(params, x.reshape(b * s, d), m, cfg.activation)
        y = y.reshape(b, s, d)
    else:
        sg = s // num_groups
        grouped = x.reshape(b, num_groups, sg, d).transpose(1, 0, 2, 3)

        def body(_, xg):
            yg, auxg = moe_group(params, xg.reshape(b * sg, d), m, cfg.activation)
            return None, (yg.reshape(b, sg, d), auxg)

        _, (y, aux) = jax.lax.scan(body, None, grouped)
        y = y.transpose(1, 0, 2, 3).reshape(b, s, d)
        aux = aux.mean()
    if "shared" in params:
        y = y + apply_mlp(params["shared"], x, cfg.activation)
    return y, aux
