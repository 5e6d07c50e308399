"""RG-LRU gated linear recurrence  h_t = a_t ⊙ h_{t-1} + b_t  — Pallas TPU.

Grid: ``(B, W/bw, S/bs)`` — batch and channel blocks are parallel; the time
axis iterates sequentially ("arbitrary") with the running hidden state ``h``
in VMEM scratch.  Within a time block the recurrence is a VPU loop over
``bs`` steps of width-``bw`` vectors (the recurrence is inherently
sequential; parallelism comes from the (B × W) grid, which for d=2560 gives
20 independent lanes per batch element at bw=128).

VMEM per program: 2·bs·bw·4B (a, b blocks) + bs·bw·4B (out) + bw·4B (h)
= ~1.5 MB at bs=256, bw=512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(loga_ref, b_ref, h0_ref, o_ref, hlast_ref, h_sc, *, bs: int, ns: int):
    t_blk = pl.program_id(2)

    @pl.when(t_blk == 0)
    def _init():
        h_sc[...] = h0_ref[0]

    a = jnp.exp(loga_ref[0].astype(jnp.float32))      # (bs, bw)
    bb = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + bb[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, h_sc[...])
    h_sc[...] = h

    @pl.when(t_blk == ns - 1)
    def _fin():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "bw", "interpret"))
def rglru_scan(log_a, b, h0, *, bs: int = 256, bw: int = 512, interpret: bool = False):
    """log_a/b: (B, S, W) f32; h0: (B, W) f32 -> (h (B,S,W), h_last (B,W))."""
    bsz, s, w = log_a.shape
    bs = min(bs, s)
    bw = min(bw, w)
    ns = pl.cdiv(s, bs)
    nw = pl.cdiv(w, bw)
    kern = functools.partial(_kernel, bs=bs, ns=ns)
    h, h_last = pl.pallas_call(
        kern,
        grid=(bsz, nw, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda b_, wi, t: (b_, t, wi)),
            pl.BlockSpec((1, bs, bw), lambda b_, wi, t: (b_, t, wi)),
            pl.BlockSpec((1, bw), lambda b_, wi, t: (b_, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda b_, wi, t: (b_, t, wi)),
            pl.BlockSpec((1, bw), lambda b_, wi, t: (b_, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b, h0)
    return h, h_last
