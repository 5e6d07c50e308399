"""KV-cache / recurrent-state pytrees.

The cache is a flat dict of stacked arrays (leading axis = layer slot) so the
CacheFlow restoration executor can slice per-layer, per-token-range views —
exactly the granularity of the paper's token/layer two-pointer plans.

Fields (present depending on architecture):
  k, v      : (n_attn, B, S_cache, H_kv, Dh)       attention KV
  ckv       : (n_attn, B, S_cache, kv_lora + rope) MLA compressed KV
  kpos      : (n_attn, S_cache) int32              position of each cache slot
                                                   (-1 = empty; ring buffer for
                                                   windowed attention)
  conv      : (n_rec, B, conv_w - 1, W)            RG-LRU conv1d tail
  lru       : (n_rec, B, W) float32                RG-LRU hidden state
  wkv       : (n_rwkv, B, H, Dh, Dh) float32       RWKV6 state matrix
  shift_tm  : (n_rwkv, B, D)                       RWKV token-shift (time mix)
  shift_cm  : (n_rwkv, B, D)                       RWKV token-shift (channel mix)

Positions/lengths are carried *outside* the cache (launcher passes them), so
the cache stays a plain array pytree.

Paged layout (DESIGN.md §12): on top of the contiguous per-request caches,
:class:`BlockPool` + :class:`PagedKVCache` provide a vLLM-style paged view
of the attention KV — fixed-size token blocks in a shared device-side pool,
per-request block tables mapping logical block index -> physical block id,
physical blocks refcounted so requests sharing a prefix alias the SAME
device memory, and copy-on-write on append so ``clone()`` forks a live
session's cache in O(1) copied bytes.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import ModelConfig

# token axis of each attention field inside a per-block payload
# (k/v/ckv: (n_attn, B, bs, ...); kpos: (n_attn, bs)) and in a contiguous
# cache; +1 in the pool slab
_TOKEN_AXIS = {"k": 2, "v": 2, "ckv": 2, "kpos": 1}


def layer_slots(cfg: ModelConfig) -> dict:
    """Map layer index -> (kind, slot index within that kind's stacked array)."""
    slots, counters = {}, {"attention": 0, "recurrent": 0, "rwkv": 0}
    for i, kind in enumerate(cfg.layer_kinds()):
        slots[i] = (kind, counters[kind])
        counters[kind] += 1
    return slots


def cache_seq_len(cfg: ModelConfig, max_len: int) -> int:
    """Windowed archs only ever hold ``attn_window`` keys (ring buffer)."""
    if cfg.attn_window:
        return min(max_len, cfg.attn_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    kinds = cfg.layer_kinds()
    n_attn = kinds.count("attention")
    n_rec = kinds.count("recurrent")
    n_rwkv = kinds.count("rwkv")
    s = cache_seq_len(cfg, max_len)
    cache: dict = {}
    if n_attn:
        if cfg.mla is not None:
            d_c = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            cache["ckv"] = jnp.zeros((n_attn, batch, s, d_c), dtype)
        else:
            cache["k"] = jnp.zeros((n_attn, batch, s, cfg.num_kv_heads, cfg.head_dim), dtype)
            cache["v"] = jnp.zeros((n_attn, batch, s, cfg.num_kv_heads, cfg.head_dim), dtype)
        cache["kpos"] = jnp.full((n_attn, s), -1, jnp.int32)
    if n_rec:
        w = cfg.rglru.lru_width or cfg.d_model
        cache["conv"] = jnp.zeros((n_rec, batch, cfg.rglru.conv1d_width - 1, w), dtype)
        cache["lru"] = jnp.zeros((n_rec, batch, w), jnp.float32)
    if n_rwkv:
        h = cfg.d_model // cfg.rwkv.head_size
        cache["wkv"] = jnp.zeros((n_rwkv, batch, h, cfg.rwkv.head_size, cfg.rwkv.head_size),
                                 jnp.float32)
        cache["shift_tm"] = jnp.zeros((n_rwkv, batch, cfg.d_model), dtype)
        cache["shift_cm"] = jnp.zeros((n_rwkv, batch, cfg.d_model), dtype)
    return cache


def cache_bytes(cache: dict) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in cache.values())


def park_cache(cache: dict) -> dict:
    """Move a (partially restored) cache off-device to host numpy buffers —
    how a preempted restoration parks WITHOUT being finalized, so suspended
    requests stop pinning device HBM while they wait for an admission slot.
    Inverse: :func:`unpark_cache`."""
    return {f: np.asarray(a) for f, a in cache.items()}


def unpark_cache(cache: dict) -> dict:
    """Return a parked cache to device arrays (dtypes preserved); resumed
    restoration ops continue writing into it exactly where they left off."""
    return {f: jnp.asarray(a) for f, a in cache.items()}


class BlockPool:
    """Shared device-side pool of fixed-size KV token blocks.

    One block holds ``block_size`` tokens' attention KV across ALL
    attention layer slots (k/v or MLA ckv, plus kpos) — the same span a
    content-addressed store chunk covers, so a store chunk promoted to HBM
    *is* a pool block and every request table that maps it aliases one
    physical copy.  Storage is one slab per field with a leading block
    axis; the slab doubles when the free list runs dry.  Blocks are
    refcounted: ``incref``/``decref`` with a free list at zero, and
    ``copy`` is the CoW primitive (counted in ``cow_copies`` /
    ``bytes_copied`` — the bytes a fork pays, which O(1)-fork tests pin).

    Field shapes are fixed by the first block written; payloads shorter
    than ``block_size`` tokens (a prefix's tail block) are zero-padded
    (kpos pads with -1 = empty slot, matching :func:`init_cache`).
    """

    def __init__(self, block_size: int, *, capacity: int = 8):
        if block_size <= 0:
            raise ValueError("block_size must be positive")
        self.block_size = block_size
        self._slabs: Optional[Dict[str, jnp.ndarray]] = None
        self._specs: Optional[Dict[str, tuple]] = None   # f -> (shape, dtype)
        self.capacity = 0
        self._init_capacity = max(1, capacity)
        self.refcounts: List[int] = []
        self._free: List[int] = []
        self.block_nbytes = 0
        self.allocs = 0
        self.frees = 0
        self.cow_copies = 0
        self.bytes_copied = 0

    # -- layout ---------------------------------------------------------
    def _pad(self, f: str, arr) -> jnp.ndarray:
        """Pad (or trim) the payload's token axis to exactly one block."""
        arr = jnp.asarray(arr)
        ax = _TOKEN_AXIS[f]
        short = self.block_size - arr.shape[ax]
        if short > 0:
            pad = [(0, 0)] * arr.ndim
            pad[ax] = (0, short)
            fill = -1 if f == "kpos" else 0
            arr = jnp.pad(arr, pad, constant_values=fill)
        elif short < 0:
            take = [slice(None)] * arr.ndim
            take[ax] = slice(0, self.block_size)
            arr = arr[tuple(take)]
        return arr

    def _ensure_slabs(self, payload: dict):
        if self._slabs is not None:
            return
        self._specs = {}
        for f, arr in payload.items():
            a = self._pad(f, arr)
            self._specs[f] = (tuple(a.shape), a.dtype)
            self.block_nbytes += int(np.prod(a.shape)) * a.dtype.itemsize
        self._grow(self._init_capacity)

    def _grow(self, extra: int):
        new = {}
        for f, (shape, dtype) in self._specs.items():
            blank = jnp.full((extra,) + shape, -1, dtype) if f == "kpos" \
                else jnp.zeros((extra,) + shape, dtype)
            new[f] = blank if self._slabs is None \
                else jnp.concatenate([self._slabs[f], blank])
        self._slabs = new
        self._free.extend(range(self.capacity, self.capacity + extra))
        self.refcounts.extend([0] * extra)
        self.capacity += extra

    def _take_slot(self) -> int:
        if not self._free:
            self._grow(max(1, self.capacity))
        bid = self._free.pop()
        assert self.refcounts[bid] == 0, bid
        self.refcounts[bid] = 1
        self.allocs += 1
        return bid

    def ensure_layout(self, payload: dict):
        """Fix the pool's field shapes/dtypes from a sample payload (padded
        to one block) without allocating; no-op once the layout is set."""
        self._ensure_slabs(payload)

    # -- lifecycle ------------------------------------------------------
    def alloc(self, payload: dict) -> int:
        """Write ``payload`` (a per-block field dict) into a fresh block;
        returns its id with refcount 1."""
        self._ensure_slabs(payload)
        bid = self._take_slot()
        for f, arr in payload.items():
            self._slabs[f] = self._slabs[f].at[bid].set(self._pad(f, arr))
        return bid

    def alloc_blank(self) -> int:
        """A fresh zeroed block (kpos = -1); the CoW append target when a
        table extends past its mapped blocks."""
        if self._slabs is None:
            raise RuntimeError("pool layout unset: alloc() a block first")
        bid = self._take_slot()
        for f, (shape, dtype) in self._specs.items():
            blank = jnp.full(shape, -1, dtype) if f == "kpos" \
                else jnp.zeros(shape, dtype)
            self._slabs[f] = self._slabs[f].at[bid].set(blank)
        return bid

    def copy(self, bid: int) -> int:
        """CoW: a new sole-owner block holding ``bid``'s bytes."""
        new = self._take_slot()
        for f in self._specs:
            self._slabs[f] = self._slabs[f].at[new].set(self._slabs[f][bid])
        self.cow_copies += 1
        self.bytes_copied += self.block_nbytes
        return new

    def incref(self, bid: int):
        assert self.refcounts[bid] > 0, f"incref of free block {bid}"
        self.refcounts[bid] += 1

    def decref(self, bid: int):
        rc = self.refcounts[bid]
        if rc <= 0:
            raise AssertionError(f"double free of block {bid}")
        self.refcounts[bid] = rc - 1
        if rc == 1:
            self.frees += 1
            self._free.append(bid)

    # -- access ---------------------------------------------------------
    def read(self, bid: int) -> dict:
        """The block's fields as device array views (one block's span)."""
        return {f: self._slabs[f][bid] for f in self._specs}

    def write_slice(self, bid: int, lo: int, hi: int, fields: dict):
        """Overwrite tokens [lo, hi) of a SOLELY-OWNED block (callers CoW
        first when the refcount is > 1)."""
        assert self.refcounts[bid] == 1, \
            f"write to shared block {bid} (refcount {self.refcounts[bid]})"
        assert 0 <= lo <= hi <= self.block_size, (lo, hi)
        for f, arr in fields.items():
            idx = [bid] + [slice(None)] * len(self._specs[f][0])
            idx[_TOKEN_AXIS[f] + 1] = slice(lo, hi)
            self._slabs[f] = self._slabs[f].at[tuple(idx)].set(jnp.asarray(arr))

    def write_token(self, bid: int, off: int, fields: dict):
        """Write one token's fields (``token_fields``) into slot ``off`` of
        a SOLELY-OWNED block: one jitted program that donates the slabs,
        so the write is in place; it compiles once per slab capacity."""
        assert self.refcounts[bid] == 1, \
            f"write to shared block {bid} (refcount {self.refcounts[bid]})"
        slabs, fields = committed((self._slabs, fields))
        self._slabs = _write_token(slabs, fields, bid, off)

    # -- accounting -----------------------------------------------------
    def live_blocks(self) -> int:
        return sum(1 for rc in self.refcounts if rc > 0)

    def audit(self):
        """No block is both free and referenced; free-list ids are unique;
        every slot is either live or on the free list."""
        assert len(self._free) == len(set(self._free)), "dup free-list ids"
        for bid in self._free:
            assert self.refcounts[bid] == 0, f"free block {bid} referenced"
        assert all(rc >= 0 for rc in self.refcounts)
        assert self.live_blocks() + len(self._free) == self.capacity, \
            (self.live_blocks(), len(self._free), self.capacity)


class PagedKVCache:
    """A request's paged view of its attention KV: a block table mapping
    logical block index (token span [i·bs, (i+1)·bs)) to a physical
    :class:`BlockPool` block, or None while the span is not yet resident.

    ``clone()`` is an O(1)-copied-bytes fork: the child copies the table
    and increfs every mapped block — both sessions then alias the same
    device memory until one of them writes (``write_span`` copies a shared
    block before mutating it: copy-on-write on append)."""

    def __init__(self, pool: BlockPool, n_tokens: int = 0):
        self.pool = pool
        self.blocks: List[Optional[int]] = [None] * self._nblocks(n_tokens)
        self.n_tokens = n_tokens

    def _nblocks(self, n: int) -> int:
        return -(-n // self.pool.block_size)

    # -- fork / free ----------------------------------------------------
    def clone(self) -> "PagedKVCache":
        child = PagedKVCache(self.pool, self.n_tokens)
        child.blocks = list(self.blocks)
        for bid in child.blocks:
            if bid is not None:
                self.pool.incref(bid)
        return child

    def free(self):
        for bid in self.blocks:
            if bid is not None:
                self.pool.decref(bid)
        self.blocks = []
        self.n_tokens = 0

    def truncate(self, n_tokens: int):
        """Drop table entries past ``n_tokens`` (releasing their refs) —
        e.g. a fork that only inherits the parent's stored prefix, not its
        decoded tail."""
        keep = self._nblocks(n_tokens)
        for bid in self.blocks[keep:]:
            if bid is not None:
                self.pool.decref(bid)
        self.blocks = self.blocks[:keep]
        self.n_tokens = min(self.n_tokens, n_tokens)

    # -- residency ------------------------------------------------------
    def _extend(self, n_tokens: int):
        need = self._nblocks(n_tokens)
        if need > len(self.blocks):
            self.blocks.extend([None] * (need - len(self.blocks)))
        self.n_tokens = max(self.n_tokens, n_tokens)

    def has_block(self, idx: int) -> bool:
        return idx < len(self.blocks) and self.blocks[idx] is not None

    def map_block(self, idx: int, bid: int):
        """Alias an existing pool block (e.g. a store chunk promoted to
        HBM) at logical index ``idx``; takes a new reference."""
        self._extend((idx + 1) * self.pool.block_size)
        old = self.blocks[idx]
        if old == bid:
            return
        self.pool.incref(bid)
        if old is not None:
            self.pool.decref(old)
        self.blocks[idx] = bid

    def missing_blocks(self, t0: int, t1: int) -> List[int]:
        bs = self.pool.block_size
        return [i for i in range(t0 // bs, self._nblocks(t1))
                if not self.has_block(i)]

    def read_block(self, idx: int) -> dict:
        return self.pool.read(self.blocks[idx])

    # -- copy-on-write append -------------------------------------------
    def writable_block(self, idx: int) -> int:
        """The sole-owner block that logical block ``idx`` writes into: a
        fresh blank block when unmapped, a copy when shared with another
        table (refcount > 1) — the writer pays one block copy, every other
        referent keeps the original bytes."""
        bid = self.blocks[idx]
        if bid is None:
            bid = self.pool.alloc_blank()
        elif self.pool.refcounts[bid] > 1:
            new = self.pool.copy(bid)
            self.pool.decref(bid)
            bid = new
        self.blocks[idx] = bid
        return bid

    def token_slot(self, pos: int) -> Tuple[int, int]:
        """``(block id, offset)`` that token ``pos`` is written to: the
        table extended to hold it, its block made writable."""
        self._extend(pos + 1)
        bs = self.pool.block_size
        return self.writable_block(pos // bs), pos % bs

    def write_span(self, t0: int, t1: int, fields: dict):
        """Write tokens [t0, t1) of the given attention fields through the
        table, each block through :meth:`writable_block` (copy-on-write)."""
        self.pool.ensure_layout(fields)
        self._extend(t1)
        bs = self.pool.block_size
        for idx in range(t0 // bs, self._nblocks(t1)):
            lo = max(t0, idx * bs) - idx * bs
            hi = min(t1, (idx + 1) * bs) - idx * bs
            bid = self.writable_block(idx)
            sliced = {}
            for f, arr in fields.items():
                ax = _TOKEN_AXIS[f]
                take = [slice(None)] * jnp.asarray(arr).ndim
                take[ax] = slice(idx * bs + lo - t0, idx * bs + hi - t0)
                sliced[f] = jnp.asarray(arr)[tuple(take)]
            self.pool.write_slice(bid, lo, hi, sliced)


def token_fields(cache: dict, pos) -> dict:
    """Traceable: token ``pos`` of a contiguous cache's attention fields,
    each with a token axis of length 1 (what ``BlockPool.write_token``
    takes)."""
    return {f: lax.dynamic_slice_in_dim(cache[f], pos, 1, axis=ax)
            for f, ax in _TOKEN_AXIS.items() if f in cache}


@partial(jax.jit, donate_argnums=0)
def _write_token(slabs: dict, fields: dict, bid, off) -> dict:
    out = {}
    for f, slab in slabs.items():
        start = [bid] + [0] * (slab.ndim - 1)
        start[_TOKEN_AXIS[f] + 1] = off
        out[f] = lax.dynamic_update_slice(
            slab, fields[f][None].astype(slab.dtype), start)
    return out


def committed(tree):
    """``tree`` with every array committed to the device it is on (no
    copy).  jit compiles committed and uncommitted inputs apart, and a
    program's outputs are committed once any input is: a program fed
    both would compile twice per shape."""
    if all(a.committed for a in jax.tree.leaves(tree)):
        return tree
    return jax.tree.map(
        lambda a: a if a.committed else jax.device_put(a, a.sharding), tree)


def grow_cache(cfg: ModelConfig, cache: dict, new_len: int) -> dict:
    """Pad the attention KV buffers (k/v/ckv/kpos) so the cache holds
    ``new_len`` tokens — how suffix prefill and decode append onto a
    restored prefix cache.  Recurrent/RWKV state fields are length-free and
    pass through; windowed archs stay capped at the ring-buffer size."""
    target = cache_seq_len(cfg, new_len)
    out = {}
    for f, a in cache.items():
        if f in ("k", "v", "ckv") and a.shape[2] < target:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, target - a.shape[2])
            out[f] = jnp.pad(a, pad)
        elif f == "kpos" and a.shape[1] < target:
            out[f] = jnp.pad(a, ((0, 0), (0, target - a.shape[1])),
                             constant_values=-1)
        else:
            out[f] = a
    return out
