"""Fused restoration dequant-scatter — Pallas TPU.

One launch restores one load op: a grid over ``(slot, chunk)`` where every
program dequantizes (or plain-copies) one store chunk's rows for one cache
slot out of the packed staging buffer and writes them in place into the
live cache via ``input_output_aliases``.  All attention fields of the op
ride the same launch as parallel (input, output) pairs, so the legacy
O(chunks x layers x fields) ``.at[].set()`` storm collapses to a single
dispatch.

Layout per field f (channels = flattened trailing axes, token axis 1):

  cache_f   (A, S, C_f)  aliased in/out — only blocks touched by the grid
                         are written; boundary blocks past S are clipped
                         by Pallas' partial-block masking, which is what
                         lets the zero-padded tail of the last prefix
                         chunk ride along safely (tails only occur when
                         the op ends exactly at S).
  staged_f  (A, T, C_f)  packed staging buffer, T = n_chunks * cs
  scales_f  (n_chunks, 1, C_f) f32 — per-chunk per-channel scales
                         (quantized path only)

Grid ``(n_slots, n_chunks)``; block shapes ``(1, cs, C_f)`` with the out
index map offset by ``(slot_lo, t0 // cs)`` so a sub-span of slots and a
mid-prefix token range address the right cache region; slots outside the
span keep their bytes (aliased, never visited by the grid).  The dequant body
is bit-identical to ``kv_quant._dequant_kernel`` (f32 multiply, one cast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _restore_kernel(nf, quant, *refs):
    out_refs = refs[-nf:]
    staged_refs = refs[nf:2 * nf]
    scales_refs = refs[2 * nf:3 * nf] if quant else ()
    for f in range(nf):
        x = staged_refs[f][...]
        if quant:
            s = scales_refs[f][...]                  # (1, 1, C_f)
            y = (x.astype(jnp.float32) * s).astype(out_refs[f].dtype)
        else:
            y = x.astype(out_refs[f].dtype)
        out_refs[f][...] = y


@functools.partial(jax.jit, static_argnames=("t0", "slot_lo", "n_slots",
                                             "cs", "interpret"))
def kv_restore_call(caches, staged, scales, *, t0: int, slot_lo: int,
                    n_slots: int, cs: int, interpret: bool = False):
    """caches/staged: tuples of (A, S, C_f) / (A, T, C_f); scales: tuple of
    (n_chunks, 1, C_f) f32 or None.  Writes slots [slot_lo, slot_lo +
    n_slots) only, so one stage of a multi-stage split restores its own
    sub-span.  T % cs == 0 and t0 % cs == 0 required (the ops wrapper
    guarantees both).  Returns the updated caches."""
    nf = len(caches)
    quant = scales is not None
    t = staged[0].shape[1]
    n_chunks = t // cs
    assert 0 <= slot_lo and slot_lo + n_slots <= staged[0].shape[0], \
        (slot_lo, n_slots, staged[0].shape)
    b0 = t0 // cs

    def _cache_map(a, i):
        return (slot_lo + a, b0 + i, 0)

    def _staged_map(a, i):
        return (slot_lo + a, i, 0)

    def _scales_map(a, i):
        return (i, 0, 0)

    cache_specs = [pl.BlockSpec((1, cs, c.shape[-1]), _cache_map)
                   for c in caches]
    staged_specs = [pl.BlockSpec((1, cs, x.shape[-1]), _staged_map)
                    for x in staged]
    in_specs = cache_specs + staged_specs
    operands = list(caches) + list(staged)
    if quant:
        in_specs += [pl.BlockSpec((1, 1, s.shape[-1]), _scales_map)
                     for s in scales]
        operands += list(scales)
    return pl.pallas_call(
        functools.partial(_restore_kernel, nf, quant),
        grid=(n_slots, n_chunks),
        in_specs=in_specs,
        out_specs=cache_specs,
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        input_output_aliases={f: f for f in range(nf)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
