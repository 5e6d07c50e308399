"""CacheFlow restoration correctness (the paper's core):
restored cache ≡ full-prefill cache for every strategy, stage count, and
legal op interleaving; first-token logits agree with the reference path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import RestorationExecutor
from repro.core.baselines import make_baseline_plans
from repro.models import build_model

ARCHS = ["qwen3-8b", "deepseek-v2-236b", "deepseek-moe-16b",
         "recurrentgemma-2b", "rwkv6-7b", "musicgen-large"]
N = 40
RNG = jax.random.PRNGKey(0)


def _setup(arch, stages=1, chunk=8):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(RNG)
    ex = RestorationExecutor(m, params, chunk_size=chunk, stages=stages)
    if cfg.input_mode == "tokens":
        inputs = jax.random.randint(RNG, (1, N), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(RNG, (1, N, cfg.d_model), jnp.float32)
    ex.remember("req", inputs)
    return cfg, m, ex


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("strategy", ["token", "layer"])
def test_restoration_matches_prefill(arch, strategy):
    cfg, m, ex = _setup(arch)
    if cfg.rwkv is not None and strategy == "token":
        pytest.skip("token pointers inapplicable to attention-free archs")
    ex.restore("req", strategy=strategy, op_order="alternate")
    ex.verify("req")


@pytest.mark.parametrize("arch", ["qwen3-8b", "recurrentgemma-2b"])
@pytest.mark.parametrize("stages", [2, 3])
def test_stage_parallel_restoration(arch, stages):
    """3D dimension: per-stage restoration from boundary activations."""
    cfg, m, ex = _setup(arch, stages=stages)
    ex.restore("req", l_delta=16)
    ex.verify("req")


@pytest.mark.property
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       order=st.sampled_from(["random", "io_first", "compute_first"]))
def test_any_interleaving_is_correct(seed, order):
    """Property: op interleaving must not affect the restored cache."""
    cfg, m, ex = _setup("qwen3-8b")
    ex.restore("req", l_delta=16, op_order=order,
               rng=np.random.default_rng(seed))
    ex.verify("req")


@pytest.mark.parametrize("system", ["vllm", "lmcache", "cake", "cacheflow"])
def test_baseline_plans_restore_correctly(system):
    """Every baseline strategy produces a correct cache (they differ in
    TIME, never in the result)."""
    cfg, m, ex = _setup("qwen3-8b")
    plans = make_baseline_plans(system, "req", N, chunk_size=8, l_delta=16,
                                num_layers=cfg.num_layers)
    ex.restore("req", plans=plans)
    ex.verify("req")


def test_first_token_matches_reference():
    """TTFT tokens from a restored engine == tokens from the cold path."""
    cfg, m, ex = _setup("qwen3-8b")
    new = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    # reference: full prefill of prefix+suffix in one go
    req = ex.store.get("req")
    full = jnp.concatenate([req.inputs, new], axis=1)
    logits_ref, _ = m.prefill(m.init(RNG), full)  # fresh params? no — same
    params = ex.params
    logits_ref, _ = m.prefill(params, full)
    # restored path
    ex.restore("req", l_delta=16)
    logits_restored = ex.first_token_logits("req", new)
    np.testing.assert_allclose(np.asarray(logits_restored, np.float32),
                               np.asarray(logits_ref, np.float32),
                               atol=3e-2, rtol=3e-2)
    assert int(jnp.argmax(logits_restored)) == int(jnp.argmax(logits_ref))


def test_boundary_activations_smaller_than_kv():
    """Paper §3.2: the boundary payload is much smaller than the stage KV."""
    cfg, m, ex = _setup("qwen3-8b", stages=2)
    req = ex.store.get("req")
    b_bytes = ex.store.boundary_bytes("req", 1)
    kv_bytes = sum(int(np.asarray(v).nbytes) for k, v in req.kv_reference.items()
                   if k in ("k", "v", "ckv"))
    assert b_bytes * 2 < kv_bytes
