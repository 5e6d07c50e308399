"""Flash attention (causal, GQA, optional cached prefix) — Pallas TPU kernel.

Semantics: queries are a chunk of ``S_q`` tokens whose absolute positions are
``q_offset + i``; keys/values cover positions ``[0, kv_len)`` (a restored
prefix followed by the chunk itself).  Token ``i`` attends to ``j`` iff
``j <= q_offset + i`` (and ``j > q_offset + i - window`` when windowed).

Grid: ``(B, Hq, nq, nk)`` — the last axis iterates key blocks sequentially
("arbitrary" semantics) with the online-softmax carry (m, l, acc) resident in
VMEM scratch.  Block shapes are MXU-aligned: q/out ``(bq, Dh)``, k/v
``(bk, Dh)`` with ``bq = bk = 128`` by default and Dh ∈ {64, 128, 256}.

VMEM budget per program ≈ (bq + 2·bk)·Dh·2B + bq·bk·4B + carry ≈ 0.3 MB at
128/128/128 — far under the ~16 MB/core VMEM, leaving room for the compiler
to double-buffer the HBM→VMEM streams of k/v blocks.

Scalars (q_offset, kv_len) arrive via scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(scalars, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
            *, bq: int, bk: int, nk: int, scale: float, window: int):
    j = pl.program_id(3)
    q_offset = scalars[0]
    kv_len = scalars[1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    i = pl.program_id(2)
    q_pos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # whole block out of causal range? skip the matmul
    block_alive = (j * bk <= q_offset + i * bq + bq - 1)

    @pl.when(block_alive)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (k_pos <= q_pos) & (k_pos < kv_len)
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        l_prev = l_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_prev * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + pv
        m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bq", "bk", "interpret"))
def flash_prefill(q, k, v, q_offset, kv_len, *, scale: float, window: int = 0,
                  bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (B, Sq, Hq, Dh); k/v: (B, Skv, Hkv, Dh); q_offset/kv_len: i32 scalars.
    Returns (B, Sq, Hq, Dh)."""
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(skv, bk)
    scalars = jnp.array([q_offset, kv_len], jnp.int32)

    grid = (b, hq, nq, nk)
    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, scale=scale, window=window)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, 1, dh), lambda b_, h, i, j, s: (b_, i, h, 0)),
                pl.BlockSpec((1, bk, 1, dh), lambda b_, h, i, j, s: (b_, j, h // g, 0)),
                pl.BlockSpec((1, bk, 1, dh), lambda b_, h, i, j, s: (b_, j, h // g, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, 1, dh), lambda b_, h, i, j, s: (b_, i, h, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, q, k, v)
