"""The main path's Pallas kernels compile for a TPU v5e.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) v5e chip at the widths ``chip_smoke.py`` serves — Qwen3-8B's
8 KV heads x 128 = 1024 channels, 18 attention slots, 128-token chunks —
and the compiled text must hold the Mosaic kernel (``tpu_custom_call``),
not an XLA fallback.  Interpret-mode parity lives in ``test_kernels.py``;
this file catches what only the chip's compiler refuses (tiling,
fast-memory limits).

The topology is described inside a module fixture, never at import, so
that test collection loads no TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

CHUNK = 128          # chip_smoke.py's restoration chunk
C = 1024             # Qwen3-8B KV channels per token per layer (8 x 128)
SLOTS = 18           # attention layers of the 18-layer cut
TOKENS = 2048        # chip_smoke.py's prefix length


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("staging", ["bf16", "int8"])
@pytest.mark.parametrize("slot_lo,n_slots", [(0, SLOTS),
                                             (SLOTS // 2, SLOTS // 2)],
                         ids=["full-span", "stage-1-sub-span"])
def test_kv_restore_compiles_for_v5e(one_chip, no_persistent_cache, staging,
                                     slot_lo, n_slots):
    from repro.kernels.kv_restore import kernel
    n_chunks = 4
    t = n_chunks * CHUNK
    caches = tuple(_sds((SLOTS, TOKENS, C), jnp.bfloat16, one_chip)
                   for _ in ("k", "v"))
    sdt = jnp.int8 if staging == "int8" else jnp.bfloat16
    staged = tuple(_sds((SLOTS, t, C), sdt, one_chip) for _ in ("k", "v"))
    scales = (tuple(_sds((n_chunks, 1, C), jnp.float32, one_chip)
                    for _ in ("k", "v")) if staging == "int8" else None)
    compiled = kernel.kv_restore_call.lower(
        caches, staged, scales, t0=8 * CHUNK, slot_lo=slot_lo,
        n_slots=n_slots, cs=CHUNK).compile()
    _assert_kernel(compiled)


def test_kv_restore_compiles_for_v5e_at_the_latent_width(one_chip,
                                                         no_persistent_cache):
    """DeepSeek-V2-Lite's latent cache: 27 slots of 512 + 64 = 576
    channels (not a multiple of the 128 lanes), 512-token chunks; a
    block spans every channel, so the kernel takes it as it is."""
    from repro.kernels.kv_restore import kernel
    slots, c, cs = 27, 576, 512
    caches = (_sds((slots, 8 * cs, c), jnp.bfloat16, one_chip),)
    staged = (_sds((slots, 2 * cs, c), jnp.bfloat16, one_chip),)
    compiled = kernel.kv_restore_call.lower(
        caches, staged, None, t0=4 * cs, slot_lo=0, n_slots=slots,
        cs=cs).compile()
    _assert_kernel(compiled)


# (rows, channels): two chunks of all KV channels, and the store's view
# of one chunk (slots x tokens x kv heads rows, head_dim channels)
QUANT_SHAPES = [(2 * CHUNK, C), (SLOTS * CHUNK * 8, 128)]


@pytest.mark.parametrize("rows,cols", QUANT_SHAPES)
def test_kv_quantize_compiles_for_v5e(one_chip, no_persistent_cache, rows,
                                      cols):
    from repro.kernels.kv_quant import kernel
    x = _sds((rows, cols), jnp.bfloat16, one_chip)
    _assert_kernel(kernel.kv_quantize_2d.lower(x).compile())


@pytest.mark.parametrize("rows,cols", QUANT_SHAPES)
def test_kv_dequantize_compiles_for_v5e(one_chip, no_persistent_cache, rows,
                                        cols):
    from repro.kernels.kv_quant import kernel
    q = _sds((rows, cols), jnp.int8, one_chip)
    s = _sds((1, cols), jnp.float32, one_chip)
    _assert_kernel(kernel.kv_dequantize_2d.lower(
        q, s, dtype=jnp.bfloat16).compile())


def test_latent_decode_compiles_for_v5e_without_copying_the_cache(
        one_chip, no_persistent_cache):
    """DeepSeek-V2-Lite's decode token at one expert-parallel chip's share
    (8 of 64 routed experts) over 8192 cached tokens: the latent cache is
    read in place, so the program's temporaries are a small part of the
    cache it attends to (the decode through ``stack_chunk`` copied the
    cache around its scan: twice the cache in temporaries)."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.executor import _decode_program
    from repro.models.model import build_model
    base = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, experts_held=8))
    m = build_model(cfg, param_dtype=jnp.bfloat16,
                    compute_dtype=jnp.bfloat16)
    assert m.latent_decode

    def shapes(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = shapes(jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: m.init_cache(1, 8192)))
    compiled = _decode_program.lower(
        params, _sds((1,), jnp.int32, one_chip), cache,
        _sds((), jnp.int32, one_chip), step=m.decode_step_jit,
        embed_dim=0).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 8
