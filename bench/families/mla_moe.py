"""Latent attention and sparse experts (DeepSeek-V2-Lite): multi-head latent
attention (MLA) without a q-LoRA in every layer, a dense gated MLP in the
leading layer and an expert layer in every later one, of which this chip
holds a contiguous share of the routed experts.  The interface is
``harness.arch``'s.

The reference's layer, from the published equations (arXiv:2405.04434 §2.1,
the DeepSeek-V2 modelling code), with K and V decompressed per head:

    h = RMSNorm(x) · g1
    q = h Wq                          (per head: q_nope 128 ‖ q_pe 64)
    c ‖ k_pe = h Wkv_a ;  c = RMSNorm(c) · g_kv         (latent 512, rope 64)
    k_nope ‖ v = c Wkv_b              (per head: 128 ‖ 128)
    q_pe, k_pe = YaRN-RoPE(q_pe), YaRN-RoPE(k_pe)       (k_pe shared by heads)
    x = x + softmax([q_nope‖q_pe]·[k_nope‖k_pe]ᵀ · s, causal) v Wo
        s = (128+64)^-½ · g(factor, mscale_all_dim)²,  g(f, m) = 0.1·m·ln f + 1
    h = RMSNorm(x) · g2
    x = x + (silu(h Wg) ⊙ h Wu) Wd                       (leading dense layer)
    x = x + Σ_{e held} r_e(h) · FFN_e(h) + FFN_shared(h) (expert layers)
        p = softmax(h W_router) over every routed expert (float32);
        r_e = p_e where e is among the greedy top-k of p, else 0
        (renormalised over the top-k only where norm_topk_prob);
        experts held elsewhere add nothing here.

YaRN: inverse frequencies ``f_inter·(1 − m) + f_extra·m`` with
``f_extra = θ^(−2i/d)``, ``f_inter = f_extra / factor`` and
``m = 1 − clip((i − low)/(high − low), 0, 1)``, ``low = ⌊c(β_fast)⌋``,
``high = ⌈c(β_slow)⌉``, ``c(r) = d·ln(L₀/(2πr)) / (2 ln θ)``; cos and sin
scaled by ``g(factor, mscale)/g(factor, mscale_all_dim)``.

Departure: the checkpoint stores each rope slice's columns interleaved and
the published code permutes them before rotate-half.  With random weights
that permutation is one of weight columns, so here rotate-half is applied
to the projection's output as it comes (the configuration's ``assumed``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from harness import arch, op_scopes, weights
from harness.reference import HI, Q_BLOCK, fq, mm, rms

# this family's cells read device time by the program's named scopes
# (device_ms_per_token.moe and .mla): the trace's load keeps them
op_scopes.keep_scopes()


@dataclass(frozen=True)
class Arch(arch.Arch):
    heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_d_ff: int
    expert_d_ff: int
    experts_total: int          # experts the router scores (published)
    experts_held: int           # routed experts this chip holds
    expert_offset: int          # the first of them
    top_k: int
    shared_experts: int
    norm_topk_prob: bool
    rope_theta: float
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What the cache stores per token and layer: latent ‖ rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def parse(d: dict) -> Arch:
    if d["q_lora_rank"]:
        raise arch.BenchError("mla_moe draws a full-rank q projection; "
                              f"q_lora_rank is {d['q_lora_rank']}")
    if int(d["first_k_dense_replace"]) != 1 or int(d["moe_layer_freq"]) != 1:
        raise arch.BenchError("mla_moe draws one leading dense layer and an "
                              "expert layer in every later one")
    if float(d["routed_scaling_factor"]) != 1:
        raise arch.BenchError("mla_moe weights the routed experts by the "
                              "router alone; routed_scaling_factor is "
                              f"{d['routed_scaling_factor']}")
    y = d["rope_scaling"]
    if y.get("type") != "yarn":
        raise arch.BenchError(f"mla_moe applies YaRN RoPE; rope_scaling "
                              f"is {y!r}")
    return Arch(
        **arch.common(d), heads=int(d["num_attention_heads"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        dense_d_ff=int(d["intermediate_size"]),
        expert_d_ff=int(d["moe_intermediate_size"]),
        experts_total=int(d["n_routed_experts_published"]),
        experts_held=int(d["n_routed_experts"]),
        expert_offset=int(d["expert_offset"]),
        top_k=int(d["num_experts_per_tok"]),
        shared_experts=int(d["n_shared_experts"]),
        norm_topk_prob=bool(d["norm_topk_prob"]),
        rope_theta=float(d["rope_theta"]),
        yarn_factor=float(y["factor"]),
        yarn_original_max=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]))


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("a",))
def draw(a: Arch, key):
    dt = jnp.dtype(a.dtype)
    d, h = a.d_model, a.heads

    def attention(key):
        k = jax.random.split(key, 7)
        r = a.kv_lora_rank
        return {"norm1": weights.scale(k[0], d, dt),
                "norm2": weights.scale(k[1], d, dt),
                "wq": weights.normal(k[2], (d, h * a.qk_head_dim),
                                     d ** -0.5, dt),
                "wkv_a": weights.normal(k[3], (d, a.latent_width),
                                        d ** -0.5, dt),
                "kv_norm": weights.scale(k[4], r, dt),
                "wkv_b": weights.normal(
                    k[5], (r, h * (a.qk_nope_head_dim + a.v_head_dim)),
                    r ** -0.5, dt),
                "wo": weights.normal(k[6], (h * a.v_head_dim, d),
                                     (h * a.v_head_dim) ** -0.5, dt)}

    def mlp(key, f, lead=()):
        k = jax.random.split(key, 3)
        return {"w_gate": weights.normal(k[0], lead + (d, f), d ** -0.5, dt),
                "w_up": weights.normal(k[1], lead + (d, f), d ** -0.5, dt),
                "w_down": weights.normal(k[2], lead + (f, d), f ** -0.5, dt)}

    def dense(key):
        k = jax.random.split(key, 2)
        return attention(k[0]) | mlp(k[1], a.dense_d_ff)

    def expert_layer(key):
        k = jax.random.split(key, 4)
        shared = mlp(k[2], a.shared_experts * a.expert_d_ff)
        # the router keeps every expert's column, in float32 as the
        # program holds it
        return (attention(k[0])
                | mlp(k[1], a.expert_d_ff, (a.experts_held,))
                | {"router": weights.normal(k[3], (d, a.experts_total),
                                            d ** -0.5, jnp.float32)}
                | {"shared_" + n: v for n, v in shared.items()})

    def layers(key):
        k = jax.random.split(key, 2)
        return {"dense": dense(k[0]),
                "moe": jax.lax.map(expert_layer,
                                   jax.random.split(k[1], a.layers - 1))}

    return weights.draw_model(a, key, layers)


_ATTN = ("wq", "wkv_a", "wkv_b", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def program_params(w: dict) -> dict:
    """The same arrays as the program's parameter tree: the dense layer
    under ``prefix_layers``, the expert layers stacked under
    ``scan_layers``; nothing is copied."""
    def layer(L):
        attn = {k: L[k] for k in _ATTN}
        attn["kv_norm"] = {"scale": L["kv_norm"]}
        return {"norm1": {"scale": L["norm1"]},
                "norm2": {"scale": L["norm2"]}, "attn": attn}

    dense, experts = w["layers"]["dense"], w["layers"]["moe"]
    moe = {k: experts[k] for k in _MLP + ("router",)}
    moe["shared"] = {k: experts["shared_" + k] for k in _MLP}
    p = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
         "prefix_layers": [layer(dense) | {"mlp": {k: dense[k]
                                                   for k in _MLP}}],
         "scan_layers": layer(experts) | {"moe": moe}}
    if "unembed" in w:
        p["unembed"] = w["unembed"]
    return p


def program_config(a: Arch):
    """The program's model config with every size taken from the file."""
    from repro.config import MLAConfig, MoEConfig, YarnConfig
    from repro.configs import get_config
    base = get_config(a.program_arch)
    return dataclasses.replace(
        base, name=a.name, num_layers=a.layers, d_model=a.d_model,
        num_heads=a.heads, num_kv_heads=a.heads, d_ff=a.dense_d_ff,
        vocab_size=a.vocab, rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        tie_embeddings=a.tied,
        rope_scaling=YarnConfig(
            factor=a.yarn_factor,
            original_max_position_embeddings=a.yarn_original_max,
            beta_fast=a.yarn_beta_fast, beta_slow=a.yarn_beta_slow,
            mscale=a.yarn_mscale, mscale_all_dim=a.yarn_mscale_all_dim),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=a.kv_lora_rank,
                      qk_nope_head_dim=a.qk_nope_head_dim,
                      qk_rope_head_dim=a.qk_rope_head_dim,
                      v_head_dim=a.v_head_dim),
        moe=dataclasses.replace(
            base.moe, num_experts=a.experts_total, top_k=a.top_k,
            expert_d_ff=a.expert_d_ff, num_shared_experts=a.shared_experts,
            shared_d_ff=a.shared_experts * a.expert_d_ff, first_k_dense=1,
            dense_d_ff=a.dense_d_ff, norm_topk_prob=a.norm_topk_prob,
            expert_offset=a.expert_offset,
            experts_held=a.experts_held))


# ---------------------------------------------------------------------------
# The float32 reference
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(a: Arch) -> np.ndarray:
    """YaRN inverse frequencies of the rope head dims (float64)."""
    d, theta = a.qk_rope_head_dim, a.rope_theta
    extra = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / a.yarn_factor

    def c(r):
        return d * math.log(a.yarn_original_max / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(a.yarn_beta_fast)), 0)
    high = min(math.ceil(c(a.yarn_beta_slow)), d - 1)
    m = 1.0 - np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                      0.0, 1.0)
    return inter * (1.0 - m) + extra * m


def softmax_scale(a: Arch) -> float:
    return a.qk_head_dim ** -0.5 * yarn_mscale(
        a.yarn_factor, a.yarn_mscale_all_dim) ** 2


def yarn_rope(x, pos, a: Arch):
    """Rotate-half RoPE with YaRN frequencies: x (T, H, d), pos (T,)."""
    d = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(a), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    g = (yarn_mscale(a.yarn_factor, a.yarn_mscale)
         / yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim))
    c, s = jnp.cos(ang) * g, jnp.sin(ang) * g
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _emm(x, w, fp8: bool):
    """x (T, D) through every held expert's w (E, D, F) -> (T, E, F)."""
    if fp8:
        x, w = fq(x, -1), fq(w, (1, 2))
    return jnp.einsum("td,edf->tef", x, w, precision=HI)


def _ffn(h, w, fp8: bool, prefix: str = ""):
    g = jax.nn.silu(mm(h, w[prefix + "w_gate"], fp8))
    return mm(g * mm(h, w[prefix + "w_up"], fp8), w[prefix + "w_down"], fp8)


def _experts(h, w, a: Arch, fp8: bool):
    """The routed experts held here, weighted by the router, plus the
    shared experts: h (T, D) -> (T, D)."""
    probs = jax.nn.softmax(mm(h, w["router"], fp8), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    held = a.expert_offset + jnp.arange(a.experts_held)
    r = jnp.sum(top_p[:, :, None] * (top_i[:, :, None] == held), axis=1)
    f = jax.nn.silu(_emm(h, w["w_gate"], fp8)) * _emm(h, w["w_up"], fp8)
    if fp8:
        f, wd = fq(f, -1), fq(w["w_down"], (1, 2))
    else:
        wd = w["w_down"]
    y = jnp.einsum("tef,efd->ted", f, wd, precision=HI)
    routed = jnp.einsum("te,ted->td", r, y, precision=HI)
    return routed + _ffn(h, w, fp8, "shared_")


def _layer_f32(w, x, pos, a: Arch, fp8: bool, dense: bool):
    """One layer over a padded sequence x (T, D) float32."""
    t = x.shape[0]
    hn, r = a.heads, a.kv_lora_rank
    h = rms(x, w["norm1"], a.norm_eps)
    ckv = mm(h, w["wkv_a"], fp8)
    c = rms(ckv[:, :r], w["kv_norm"], a.norm_eps)
    k_pe = yarn_rope(ckv[:, None, r:], pos, a)                  # (T, 1, rope)
    kv = mm(c, w["wkv_b"], fp8).reshape(
        t, hn, a.qk_nope_head_dim + a.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :a.qk_nope_head_dim],
         jnp.broadcast_to(k_pe, (t, hn, a.qk_rope_head_dim))], axis=-1)
    v = kv[..., a.qk_nope_head_dim:]
    if fp8:
        k, v = fq(k, -1), fq(v, (0, 2))
    scale = softmax_scale(a)

    def block(args):
        xb, hb, pb = args
        q = mm(hb, w["wq"], fp8).reshape(-1, hn, a.qk_head_dim)
        q = jnp.concatenate([q[..., :a.qk_nope_head_dim],
                             yarn_rope(q[..., a.qk_nope_head_dim:], pb, a)],
                            axis=-1)
        if fp8:
            q = fq(q, -1)
        sc = jnp.einsum("qhd,thd->hqt", q, k, precision=HI) * scale
        causal = pos[None, :] <= pb[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = fq(p, -1)
        o = jnp.einsum("hqt,thd->qhd", p, v, precision=HI)
        xb = xb + mm(o.reshape(-1, hn * a.v_head_dim), w["wo"], fp8)
        hb = rms(xb, w["norm2"], a.norm_eps)
        return xb + (_ffn(hb, w, fp8) if dense else _experts(hb, w, a, fp8))

    nb = t // Q_BLOCK
    out = jax.lax.map(block, (x.reshape(nb, Q_BLOCK, -1),
                              h.reshape(nb, Q_BLOCK, -1),
                              pos.reshape(nb, Q_BLOCK)))
    return out.reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _dense_layer(layer, x, pos, *, a: Arch, fp8: bool):
    w = jax.tree.map(lambda t: t.astype(jnp.float32), layer)
    return _layer_f32(w, x, pos, a, fp8, dense=True)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _expert_layer(stack, li, x, pos, *, a: Arch, fp8: bool):
    w = jax.tree.map(lambda t: t[li].astype(jnp.float32), stack)
    return _layer_f32(w, x, pos, a, fp8, dense=False)


def layers(w: dict, x, pos, a: Arch, fp8: bool):
    """Every layer in turn over the padded sequence x (T, D) float32."""
    x = _dense_layer(w["layers"]["dense"], x, pos, a=a, fp8=fp8)
    for li in range(a.layers - 1):
        x = _expert_layer(w["layers"]["moe"], li, x, pos, a=a, fp8=fp8)
    return x


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------


def _attention_flops(a: Arch, ctx: int) -> int:
    """MLA as published (K and V decompressed): the q, latent, up and
    output projections of one token, and scores and weighted sum over
    ``ctx`` keys."""
    d, h = a.d_model, a.heads
    proj = (d * h * a.qk_head_dim + d * a.latent_width
            + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)
            + h * a.v_head_dim * d)
    return 2 * proj + 2 * h * (a.qk_head_dim + a.v_head_dim) * ctx


def token_flops(a: Arch, ctx: int) -> float:
    """One token through every layer, attending to ``ctx`` keys (itself
    included).  The expert layers count the router, the shared experts,
    and the routed experts a token selects on average among those held
    here (top_k × held / total of them): what the work requires, not
    what the program's dropless buffer computes (every held expert, on a
    slot per token)."""
    d = a.d_model
    gated = 3 * 2 * d                    # gate, up and down: 2·3·d·width
    dense = gated * a.dense_d_ff
    per_token_experts = a.top_k * a.experts_held / a.experts_total
    expert = (2 * d * a.experts_total
              + gated * a.expert_d_ff * (per_token_experts
                                         + a.shared_experts))
    return float(a.layers * _attention_flops(a, ctx) + dense
                 + (a.layers - 1) * expert)


def head_flops(a: Arch) -> float:
    """Logits of one position over the whole vocabulary."""
    return float(2 * a.d_model * a.vocab)


def kv_bytes_per_token_layer(a: Arch, itemsize: int) -> int:
    """The latent and the rope key of one token in one layer."""
    return a.latent_width * itemsize


def kv_scale_bytes_per_chunk(a: Arch) -> int:
    """One f32 scale per stored channel of an int8 chunk."""
    return a.latent_width * 4
