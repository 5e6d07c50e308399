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
