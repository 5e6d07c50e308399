"""Dense GQA transformer: grouped-query attention, optionally with
RMSNorm on q and k (QK-norm), and a gated SiLU MLP, the same in every
layer (Qwen3, Phi-4-mini).  The interface is ``harness.arch``'s.

The reference's layer, from the published equations:

    h = RMSNorm(x) · g1
    q, k, v = h Wq, h Wk, h Wv          (per head; RMSNorm over head_dim on q
                                          and k where the model has QK-norm)
    q, k = RoPE(q), RoPE(k)              (rotate-half form, all head dims)
    x = x + softmax(q kᵀ / √d_head, causal) v Wo
    h = RMSNorm(x) · g2
    x = x + (silu(h Wg) ⊙ h Wu) Wd
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from harness import arch, weights
from harness.reference import HI, Q_BLOCK, fq, mm, rms, rope


@dataclass(frozen=True)
class Arch(arch.Arch):
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    qk_norm: bool

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim


def parse(d: dict) -> Arch:
    return Arch(
        **arch.common(d), heads=int(d["num_attention_heads"]),
        kv_heads=int(d["num_key_value_heads"]), head_dim=int(d["head_dim"]),
        d_ff=int(d["intermediate_size"]), rope_theta=float(d["rope_theta"]),
        qk_norm=bool(d["qk_norm"]))


@functools.partial(jax.jit, static_argnames=("a",))
def draw(a: Arch, key):
    dt = jnp.dtype(a.dtype)

    def layer(key):
        k = jax.random.split(key, 11)
        d, q, kv, f = a.d_model, a.q_width, a.kv_width, a.d_ff
        w = {"norm1": weights.scale(k[0], d, dt),
             "norm2": weights.scale(k[1], d, dt),
             "wq": weights.normal(k[2], (d, q), d ** -0.5, dt),
             "wk": weights.normal(k[3], (d, kv), d ** -0.5, dt),
             "wv": weights.normal(k[4], (d, kv), d ** -0.5, dt),
             "wo": weights.normal(k[5], (q, d), q ** -0.5, dt),
             "w_gate": weights.normal(k[6], (d, f), d ** -0.5, dt),
             "w_up": weights.normal(k[7], (d, f), d ** -0.5, dt),
             "w_down": weights.normal(k[8], (f, d), f ** -0.5, dt)}
        if a.qk_norm:
            w["q_norm"] = weights.scale(k[9], a.head_dim, dt)
            w["k_norm"] = weights.scale(k[10], a.head_dim, dt)
        return w

    return weights.draw_model(
        a, key, lambda k: jax.lax.map(layer, jax.random.split(k, a.layers)))


def program_params(w: dict) -> dict:
    """The same arrays arranged as the program's parameter tree (a
    uniform layer stack under ``scan_layers``); nothing is copied."""
    L = w["layers"]
    attn = {k: L[k] for k in ("wq", "wk", "wv", "wo")}
    if "q_norm" in L:
        attn["q_norm"], attn["k_norm"] = L["q_norm"], L["k_norm"]
    p = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
         "prefix_layers": [],
         "scan_layers": {"norm1": {"scale": L["norm1"]},
                         "norm2": {"scale": L["norm2"]}, "attn": attn,
                         "mlp": {k: L[k] for k in ("w_gate", "w_up",
                                                   "w_down")}}}
    if "unembed" in w:
        p["unembed"] = w["unembed"]
    return p


def program_config(a: Arch):
    """The program's model config with every size taken from the file."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(a.program_arch), name=a.name, num_layers=a.layers,
        d_model=a.d_model, num_heads=a.heads, num_kv_heads=a.kv_heads,
        head_dim=a.head_dim, d_ff=a.d_ff, vocab_size=a.vocab,
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        tie_embeddings=a.tied, use_qk_norm=a.qk_norm)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _layer(layers, li, x, pos, *, a: Arch, fp8: bool):
    """One layer over a padded sequence x (T, D) float32."""
    w = jax.tree.map(lambda t: t[li].astype(jnp.float32), layers)
    t = x.shape[0]
    g = a.heads // a.kv_heads
    h = rms(x, w["norm1"], a.norm_eps)
    k = mm(h, w["wk"], fp8).reshape(t, a.kv_heads, a.head_dim)
    v = mm(h, w["wv"], fp8).reshape(t, a.kv_heads, a.head_dim)
    if a.qk_norm:
        k = rms(k, w["k_norm"], a.norm_eps)
    k = rope(k, pos, a.rope_theta)
    if fp8:
        k, v = fq(k, -1), fq(v, (0, 2))
    scale = a.head_dim ** -0.5

    def block(args):
        xb, hb, pb = args
        q = mm(hb, w["wq"], fp8).reshape(-1, a.heads, a.head_dim)
        if a.qk_norm:
            q = rms(q, w["q_norm"], a.norm_eps)
        q = rope(q, pb, a.rope_theta)
        if fp8:
            q = fq(q, -1)
        qg = q.reshape(-1, a.kv_heads, g, a.head_dim)
        sc = jnp.einsum("qkgd,tkd->kgqt", qg, k, precision=HI) * scale
        causal = pos[None, :] <= pb[:, None]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = fq(p, -1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
        xb = xb + mm(o.reshape(-1, a.q_width), w["wo"], fp8)
        hb = rms(xb, w["norm2"], a.norm_eps)
        f = jax.nn.silu(mm(hb, w["w_gate"], fp8)) * mm(hb, w["w_up"], fp8)
        return xb + mm(f, w["w_down"], fp8)

    nb = t // Q_BLOCK
    out = jax.lax.map(block, (x.reshape(nb, Q_BLOCK, -1),
                              h.reshape(nb, Q_BLOCK, -1),
                              pos.reshape(nb, Q_BLOCK)))
    return out.reshape(t, -1)


def layers(w: dict, x, pos, a: Arch, fp8: bool):
    """Every layer in turn over the padded sequence x (T, D) float32."""
    for li in range(a.layers):
        x = _layer(w["layers"], li, x, pos, a=a, fp8=fp8)
    return x


def _matmul_params_per_layer(a: Arch) -> int:
    """Weights of one layer's matrix multiplications (q, k, v, o and the
    gated MLP); norm scales are not multiplied and are left out."""
    return (a.d_model * a.q_width + 2 * a.d_model * a.kv_width
            + a.q_width * a.d_model + 3 * a.d_model * a.d_ff)


def token_flops(a: Arch, ctx: int) -> float:
    """One token through every layer, attending to ``ctx`` keys (itself
    included): the projections and MLP, plus scores and the weighted sum
    over the context."""
    per_layer = 2 * _matmul_params_per_layer(a) + 4 * a.q_width * ctx
    return float(a.layers * per_layer)


def head_flops(a: Arch) -> float:
    """Logits of one position over the whole vocabulary."""
    return float(2 * a.d_model * a.vocab)


def kv_bytes_per_token_layer(a: Arch, itemsize: int) -> int:
    """K and V of one token in one layer."""
    return 2 * a.kv_width * itemsize


def kv_scale_bytes_per_chunk(a: Arch) -> int:
    """One f32 scale per head channel, for K and for V, of an int8
    chunk."""
    return 2 * a.head_dim * 4
