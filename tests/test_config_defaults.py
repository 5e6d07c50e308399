"""The expert share, YaRN RoPE and the prefix-then-scan stack leave every
configuration that existed before them bit-identical.

``tests/data/golden_configs.json`` holds, for each registered architecture
of that time at its reduced size, the sha256 of its initial parameters and
of its logits: a whole-sequence forward, and a chunk prefill followed by
single-token decode steps against the cache (the served path's
``stack_chunk``).  They were recorded by :func:`fingerprint` from the code
before ``MoEConfig.expert_offset`` / ``experts_held`` / ``norm_topk_prob``
and ``ModelConfig.rope_scaling`` existed; the new
defaults must reproduce them exactly.

One path changed on purpose: a model with leading dense layers
(DeepSeek's ``first_k_dense``) now decodes its whole stack as the unrolled
prefix and then one scan, where it unrolled every layer, and an MLA model
among them (``deepseek-v2-236b``) decodes with its latent cache read in
place, the new token's latent under the same softmax (``Model.
_decode_latent``).  XLA may order float32 sums differently in the two
programs, so for those models the golden file keeps the decode logits
themselves, compared to within ``DECODE_ATOL`` (a few float32 ulps of
logits below 1 in magnitude; the measured difference was 6e-7).
"""
from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "golden_configs.json")
PREFILL, DECODE = 8, 4
DECODE_ATOL = 4e-6


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def fingerprint(arch: str) -> dict:
    """sha256 of the reduced config's parameters, forward logits, and
    prefill-then-decode logits, and those decode logits."""
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    n = PREFILL + DECODE
    rng = np.random.default_rng(7)
    if cfg.input_mode == "tokens":
        inputs = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)),
                             jnp.int32)
    else:
        inputs = jnp.asarray(rng.standard_normal((1, n, cfg.d_model)),
                             jnp.float32)
    logits, _ = m.forward(params, inputs)
    cache = m.init_cache(1, n)
    first, cache = m.prefill_chunk(params, inputs[:, :PREFILL], cache, 0)
    steps = [first]
    for t in range(PREFILL, n):
        out, cache = m.decode_step(params, inputs[:, t], cache, t)
        steps.append(out)
    return {"params": _sha(jax.tree.leaves(params)),
            "forward": _sha([logits]),
            "decode": _sha(steps),
            "decode_logits": np.concatenate([np.asarray(s) for s in steps])}


with open(GOLDEN) as _f:
    _GOLDEN = json.load(_f)


@pytest.mark.parametrize("arch", sorted(_GOLDEN))
def test_existing_config_unchanged(arch):
    got, want = fingerprint(arch), _GOLDEN[arch]
    assert got["params"] == want["params"]
    assert got["forward"] == want["forward"]
    if "decode_logits" in want:          # leading dense layers: now a scan
        np.testing.assert_allclose(got["decode_logits"],
                                   np.asarray(want["decode_logits"]),
                                   rtol=0, atol=DECODE_ATOL)
    else:
        assert got["decode"] == want["decode"]
