"""RWKV-6 wkv recurrence — Pallas TPU kernel.

    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    y_t = S_{t-1}ᵀ r_t + (r_t · (u ⊙ k_t)) v_t

Grid: ``(B, H, S/bs)`` — the (Dh × Dh) state matrix of each (batch, head)
lives in VMEM scratch across the sequential time axis.  Per time step the
update is an outer product + elementwise decay (VPU); r/k/v/w arrive as
(bs, Dh) VMEM blocks.

VMEM per program: 4·bs·Dh·4B + Dh²·4B + bs·Dh·4B ≈ 0.35 MB at bs=256, Dh=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, slast_ref, s_sc,
            *, bs: int, ns: int):
    t_blk = pl.program_id(2)

    @pl.when(t_blk == 0)
    def _init():
        s_sc[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0, :].astype(jnp.float32)      # (bs, Dh)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)               # (Dh,)

    def step(t, s):
        r_t, k_t, v_t, w_t = r[t], k[t], v[t], w[t]
        # y = Sᵀ r  +  (r · (u ⊙ k)) v
        y = jnp.dot(r_t, s) + (r_t * u * k_t).sum() * v_t
        y_ref[0, t, 0, :] = y.astype(y_ref.dtype)
        s = s * w_t[:, None] + k_t[:, None] * v_t[None, :]
        return s

    s = jax.lax.fori_loop(0, bs, step, s_sc[...])
    s_sc[...] = s

    @pl.when(t_blk == ns - 1)
    def _fin():
        slast_ref[0, 0] = s.astype(slast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def wkv6(r, k, v, w, u, s0, *, bs: int = 256, interpret: bool = False):
    """r/k/v/w: (B,S,H,Dh) f32; u: (H,Dh); s0: (B,H,Dh,Dh).
    Returns (y (B,S,H,Dh), s_last (B,H,Dh,Dh))."""
    b, s, h, dh = r.shape
    bs = min(bs, s)
    ns = pl.cdiv(s, bs)
    kern = functools.partial(_kernel, bs=bs, ns=ns)
    y, s_last = pl.pallas_call(
        kern,
        grid=(b, h, ns),
        in_specs=[
            pl.BlockSpec((1, bs, 1, dh), lambda b_, h_, t: (b_, t, h_, 0)),
            pl.BlockSpec((1, bs, 1, dh), lambda b_, h_, t: (b_, t, h_, 0)),
            pl.BlockSpec((1, bs, 1, dh), lambda b_, h_, t: (b_, t, h_, 0)),
            pl.BlockSpec((1, bs, 1, dh), lambda b_, h_, t: (b_, t, h_, 0)),
            pl.BlockSpec((1, dh), lambda b_, h_, t: (h_, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b_, h_, t: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, 1, dh), lambda b_, h_, t: (b_, t, h_, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b_, h_, t: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, s_last
