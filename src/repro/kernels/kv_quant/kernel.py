"""Per-channel int8 KV quantize / dequantize — Pallas TPU.

Operates on a 2D view ``(R, C)`` where C is the channel (last) axis of the
KV chunk; the ops wrapper reshapes/pads.  Quantization needs the global
per-channel absmax before any element can be scaled, so it is two
``pallas_call``s over the same row-block grid:

  1. ``_absmax_kernel`` — sequential row-block reduction into a (1, C)
     accumulator (init on the first block, max-accumulate after);
  2. ``_quant_kernel``  — elementwise scale+round+clip to int8 with the
     (1, C) scales broadcast to every block.

Dequantize is a single elementwise pass.  VMEM per program ≈ br·C·4B —
0.13 MB at br=256, C=128.  Rows are padded to the block size by the
wrapper (zero rows are absmax-neutral); on real TPUs C should be a
multiple of 128 (lane width) — the wrapper pads channels too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _absmax_kernel(x_ref, amax_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    blk = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=0,
                  keepdims=True)
    amax_ref[...] = jnp.maximum(amax_ref[...], blk)


def _quant_kernel(x_ref, scales_ref, q_ref):
    s = scales_ref[...]                              # (1, C)
    y = jnp.round(x_ref[...].astype(jnp.float32) / s)
    q_ref[...] = jnp.clip(y, -127, 127).astype(jnp.int8)


def _dequant_kernel(q_ref, scales_ref, o_ref):
    s = scales_ref[...]                              # (1, C)
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def kv_quantize_2d(x, *, br: int = 256, interpret: bool = False):
    """x: (R, C) float, R a multiple of br.  Returns (q int8 (R, C),
    scales f32 (1, C))."""
    r, c = x.shape
    br = min(br, r)
    nr = pl.cdiv(r, br)
    amax = pl.pallas_call(
        _absmax_kernel,
        grid=(nr,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
    scales = jnp.maximum(amax, 1e-12) / 127.0
    q = pl.pallas_call(
        _quant_kernel,
        grid=(nr,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scales)
    return q, scales


@functools.partial(jax.jit, static_argnames=("dtype", "br", "interpret"))
def kv_dequantize_2d(q, scales, *, dtype=jnp.bfloat16, br: int = 256,
                     interpret: bool = False):
    """q: (R, C) int8; scales: (1, C) f32.  Returns (R, C) ``dtype``."""
    r, c = q.shape
    br = min(br, r)
    nr = pl.cdiv(r, br)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(nr,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, scales)
