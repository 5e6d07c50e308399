"""GQA decode attention (one query token, blocked cache scan) — Pallas TPU.

The cache may be a ring buffer: validity/order come from a ``kpos`` array
(absolute position per slot, -1 = empty) instead of assuming contiguity —
slot ``j`` is visible iff ``0 <= kpos[j] <= q_pos`` (and within the window).

Grid: ``(B, Hkv, nk)`` — key blocks iterate sequentially with the
online-softmax carry in VMEM scratch; all ``G = Hq/Hkv`` query heads of a KV
group are processed together so the cache block is loaded once per group
(the GQA arithmetic-intensity trick: G ≥ 8 keeps the (G × bk) score matmul
on the MXU).

VMEM per program ≈ 2·bk·Dh·2B + G·Dh·4B ≈ 0.13 MB at bk=256, Dh=128, G=8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(scalars, q_ref, k_ref, v_ref, kpos_ref, o_ref, m_sc, l_sc, acc_sc,
            *, bk: int, nk: int, scale: float, window: int):
    j = pl.program_id(2)
    q_pos = scalars[0]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                  # (G, Dh)
    k = k_ref[0, :, 0, :].astype(jnp.float32)         # (bk, Dh)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    kp = kpos_ref[...]                                # (bk,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, bk)
    valid = (kp >= 0) & (kp <= q_pos)
    if window > 0:
        valid &= kp > q_pos - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_sc[...] = acc_sc[...] * corr[:, None] + pv
    m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bk", "interpret"))
def flash_decode(q, k, v, kpos, q_pos, *, scale: float, window: int = 0,
                 bk: int = 256, interpret: bool = False):
    """q: (B, Hq, Dh); k/v: (B, S, Hkv, Dh); kpos: (S,) i32; q_pos: i32 scalar.
    Returns (B, Hq, Dh)."""
    b, hq, dh = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bk = min(bk, s)
    nk = pl.cdiv(s, bk)
    scalars = jnp.array([q_pos], jnp.int32)

    kern = functools.partial(_kernel, bk=bk, nk=nk, scale=scale, window=window)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nk),
            in_specs=[
                pl.BlockSpec((1, g, dh), lambda b_, h, j, sc: (b_, h, 0)),
                pl.BlockSpec((1, bk, 1, dh), lambda b_, h, j, sc: (b_, j, h, 0)),
                pl.BlockSpec((1, bk, 1, dh), lambda b_, h, j, sc: (b_, j, h, 0)),
                pl.BlockSpec((bk,), lambda b_, h, j, sc: (j,)),
            ],
            out_specs=pl.BlockSpec((1, g, dh), lambda b_, h, j, sc: (b_, h, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, q, k, v, kpos)
    return out
