"""Public wrappers for the per-channel int8 KV quantizer.

Any-rank arrays are viewed as (rows, channels) with channels = the last
axis; rows are padded to the kernel block (zero rows are absmax-neutral)
and, on the Pallas path, channels are padded to the TPU lane width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.kv_quant import kernel, ref

_LANE = 128


def _pad2d(x2, br):
    r, c = x2.shape
    pr = (-r) % br
    pc = (-c) % _LANE
    if pr or pc:
        x2 = jnp.pad(x2, ((0, pr), (0, pc)))
    return x2, r, c


def resolve_backend(backend: str = "auto") -> str:
    """``auto`` is the Pallas kernel on TPU and the jnp oracle elsewhere."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def kv_quantize(x, *, backend: str = "auto", br: int = 256):
    """Per-channel int8 quantization of a KV chunk.  Returns
    (q int8, shape of ``x``; scales f32, shape ``(x.shape[-1],)``)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return ref.kv_quantize_ref(x)
    x2 = x.reshape(-1, x.shape[-1])
    xp, r, c = _pad2d(x2, br)
    q, scales = kernel.kv_quantize_2d(xp, br=br,
                                      interpret=(backend == "interpret"))
    return q[:r, :c].reshape(x.shape), scales[0, :c]


def kv_dequantize(q, scales, dtype=jnp.bfloat16, *, backend: str = "auto",
                  br: int = 256):
    """Inverse of :func:`kv_quantize` (lossy)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return ref.kv_dequantize_ref(q, scales, dtype)
    q2 = q.reshape(-1, q.shape[-1])
    qp, r, c = _pad2d(q2, br)
    sp = jnp.pad(scales[None].astype(jnp.float32),
                 ((0, 0), (0, qp.shape[1] - c)), constant_values=1.0)
    out = kernel.kv_dequantize_2d(qp, sp, dtype=dtype, br=br,
                                  interpret=(backend == "interpret"))
    return out[:r, :c].reshape(q.shape)
