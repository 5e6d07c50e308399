"""Public wrapper for the fused restoration dequant-scatter.

``kv_restore_scatter`` takes per-field 3D cache views ``(A, S, C_f)``
(token axis 1, channels flattened last), the op's packed staging buffers
``(A, T, C_f)`` and optional per-chunk scales ``(n_chunks, C_f)``, and
returns the caches with tokens ``[t0, t0 + T)`` of slots
``[slot_lo, slot_lo + n_slots)`` replaced by the dequantized payload.
Rows past S (padding in the last chunk of a prefix) are dropped.

Backend convention follows ``kv_quant``: ``auto`` uses the Pallas kernel
only on real TPUs (interpret mode is far slower than XLA on CPU) and
otherwise the jitted oracle, which XLA still fuses into one
dequant+dynamic-update-slice per field — already a single dispatch per
field instead of one per chunk x field.  The Pallas path additionally
requires sublane-aligned chunks and a chunk-aligned token range; anything
else runs the oracle.  Any channel count is fine: a block spans all of a
field's channels, which the TPU accepts at any width (an MLA latent's 576
channels as well as a K/V head group's 1024).
:func:`pick_backend` says which one a call will take, so callers can count
oracle runs instead of mistaking them for kernel launches.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.kv_restore import kernel, ref

_SUBLANE = 8


def _pallas_ok(*, t0, chunk_size, t):
    return not (t % chunk_size or t0 % chunk_size or chunk_size % _SUBLANE)


def pick_backend(caches, staged, *, t0: int, chunk_size: int,
                 backend: str = "auto") -> str:
    """The backend a scatter of these shapes runs on: ``auto`` resolves to
    ``pallas`` on TPU and ``ref`` elsewhere, and ``pallas`` becomes ``ref``
    for unaligned chunks or token ranges.  The caches' channel counts do
    not enter: a block spans all of them."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "pallas" and not _pallas_ok(
            t0=t0, chunk_size=chunk_size, t=staged[0].shape[1]):
        backend = "ref"
    return backend


@functools.partial(jax.jit, static_argnames=("t0", "slot_lo", "n_slots",
                                             "chunk_size"))
def _ref_all(caches, staged, scales, *, t0, slot_lo, n_slots, chunk_size):
    sc = scales if scales is not None else (None,) * len(caches)
    return [ref.kv_restore_ref(c, x, s, t0=t0, slot_lo=slot_lo,
                               n_slots=n_slots, chunk_size=chunk_size)
            for c, x, s in zip(caches, staged, sc)]


def kv_restore_scatter(caches, staged, scales=None, *, t0: int,
                       slot_lo: int = 0, n_slots: int | None = None,
                       chunk_size: int, backend: str = "auto"):
    """Fused dequant-scatter of one load op into the live cache views."""
    caches = tuple(caches)
    staged = tuple(staged)
    if n_slots is None:
        n_slots = caches[0].shape[0] - slot_lo
    backend = pick_backend(caches, staged, t0=t0, chunk_size=chunk_size,
                           backend=backend)
    if backend == "ref":
        return _ref_all(caches, staged,
                        None if scales is None else tuple(scales),
                        t0=t0, slot_lo=slot_lo, n_slots=n_slots,
                        chunk_size=chunk_size)
    t = staged[0].shape[1]
    assert t % chunk_size == 0 and t0 % chunk_size == 0, (t, t0, chunk_size)
    sc = None
    if scales is not None:
        sc = tuple(s.astype(jax.numpy.float32)[:, None, :] for s in scales)
    return kernel.kv_restore_call(caches, staged, sc, t0=t0,
                                  slot_lo=slot_lo, n_slots=n_slots,
                                  cs=chunk_size,
                                  interpret=(backend == "interpret"))
