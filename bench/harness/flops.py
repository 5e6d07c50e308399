"""Operations and bytes the algorithm needs, computed from shapes.

These are the numerators of the roofline shares and of ``mfu``: what the
work requires, not what the program happens to move.  A multiply-add is
two operations.  What one token of a model needs is its family's
``token_flops`` and ``head_flops`` (``bench/families/``).
"""
from __future__ import annotations

from harness.arch import Arch, family_of


def turn_flops(a: Arch, prefix: int, suffix: int, outputs: int) -> float:
    """What one served turn requires: the new suffix on top of the
    restored prefix, the logits of its last token (the first output), and
    ``outputs - 1`` decode steps, each with its logits.  Restoring the
    prefix is left out: counting recompute would reward recomputing what
    could be loaded."""
    fam = family_of(a)
    total = 0.0
    for i in range(suffix):
        total += fam.token_flops(a, prefix + i + 1)
    total += fam.head_flops(a)
    for k in range(1, outputs):
        total += fam.token_flops(a, prefix + suffix + k) + fam.head_flops(a)
    return total


def kv_restore_bytes(n_slots: int, rows: int, channels: int,
                     staged_itemsize: int, cache_itemsize: int,
                     n_chunks: int, quantized: bool) -> int:
    """One field of one ``kv_restore`` launch: read the staged rows, write
    them into the cache, and read the per-chunk scales once when the rows
    are int8."""
    nbytes = n_slots * rows * channels * (staged_itemsize + cache_itemsize)
    if quantized:
        nbytes += n_chunks * channels * 4
    return nbytes


def kv_quant_bytes(rows: int, channels: int, itemsize: int) -> int:
    """One ``kv_quantize`` call: read the chunk once, write its int8 codes
    and one f32 scale per channel."""
    return rows * channels * (itemsize + 1) + channels * 4
