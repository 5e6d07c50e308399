"""The plain reference: a float32 forward pass written from the published
equations and nothing of the program.

    x = E[tokens]
    x = layers(x)                        (the family's, bench/families/)
    logits = RMSNorm(x) · g_f  E ᵀ  (tied)  or  · U  (untied)

Every multiplication runs at ``Precision.HIGHEST`` (float32 on the TPU's
matrix unit).  The sequence runs layer by layer (one layer's weights are
cast to float32 at a time, so the reference fits beside the bfloat16
weights) and attention in blocks of ``Q_BLOCK`` queries.  Lengths are
padded to a bucket so that few programs compile; padded tokens come after
every real one and causality keeps them out of real positions.

``mode="fp8"`` is the control: the same pass with every matrix
multiplication's operands rounded to float8 e4m3 (weights scaled per
tensor, activations per row), the precision below bfloat16.  The helpers
here (``mm``, ``fq``, ``rms``, ``rope``) are what the families' layers are
written with.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness.arch import Arch, family_of

HI = jax.lax.Precision.HIGHEST
BUCKET = 1024
Q_BLOCK = 512
F8_MAX = 448.0


def fq(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8: bool):
    """x (..., K) @ w (K, N), both float32."""
    if fp8:
        x, w = fq(x, -1), fq(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x (T, H, Dh); positions (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("a",))
def _embed(table, tokens, *, a: Arch):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _head(w, x, rows, *, a: Arch, fp8: bool):
    h = rms(x[rows], w["final_norm"].astype(jnp.float32), a.norm_eps)
    if a.tied:
        table = w["embed"].astype(jnp.float32).T
    else:
        table = w["unembed"].astype(jnp.float32)
    return mm(h, table, fp8)


def logits_at(w: dict, a: Arch, tokens: np.ndarray, rows: Sequence[int],
              mode: str = "f32") -> np.ndarray:
    """Reference logits (len(rows), vocab) of the sequence ``tokens`` at
    positions ``rows``; ``mode`` is ``"f32"`` or the ``"fp8"`` control."""
    fp8 = {"f32": False, "fp8": True}[mode]
    n = len(tokens)
    t = -(-n // BUCKET) * BUCKET
    toks = np.zeros(t, np.int32)
    toks[:n] = tokens
    pos = jnp.arange(t, dtype=jnp.int32)
    x = _embed(w["embed"], jnp.asarray(toks), a=a)
    x = family_of(a).layers(w, x, pos, a, fp8)
    r = np.zeros(-(-len(rows) // 64) * 64, np.int32)
    r[:len(rows)] = rows
    head_w = {k: w[k] for k in ("final_norm", "embed", "unembed") if k in w}
    out = _head(head_w, x, jnp.asarray(r), a=a, fp8=fp8)
    return np.asarray(out[:len(rows)])


def compare(w: dict, a: Arch, prompts: List[np.ndarray],
            served: List[List[int]], logits: List[np.ndarray],
            control: bool = False) -> dict:
    """For each turn (prompt ids, served ids, the program's logits at each
    served position), against the reference at the same positions:

    * ``widest_logit_gap`` — the most a served token's reference logit
      lies below the reference's best;
    * ``widest_logit_error`` — the largest difference between a logit of
      the program and the reference's.

    With ``control``, the same two for the fp8 control put in the
    program's place (the token it puts first, and its logits)."""
    out = {"widest_logit_gap": 0.0, "widest_logit_error": 0.0,
           "control_gap": 0.0, "control_error": 0.0}
    for prompt, toks, prog in zip(prompts, served, logits):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        rows = list(range(len(prompt) - 1, len(seq)))
        ref = logits_at(w, a, seq, rows)
        best = ref.max(axis=1)
        idx = np.arange(len(toks))

        def worst(key, value):
            out[key] = max(out[key], float(value))

        worst("widest_logit_gap", np.max(best - ref[idx, np.asarray(toks)]))
        worst("widest_logit_error",
              np.max(np.abs(prog.astype(np.float32) - ref)))
        if control:
            ctl = logits_at(w, a, seq, rows, mode="fp8")
            worst("control_gap", np.max(best - ref[idx, ctl.argmax(axis=1)]))
            worst("control_error", np.max(np.abs(ctl - ref)))
    return out
