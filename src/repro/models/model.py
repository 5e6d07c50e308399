"""Model builder: config -> functional model with train / prefill / decode /
restoration-chunk entry points.

Layer organisation:
  * uniform archs (most of the pool): parameters of identical layers are
    stacked on a leading axis and executed with ``jax.lax.scan`` — compact
    HLO, fast compiles, and the idiom FSDP weight-gathering optimises well.
  * a non-uniform *prefix* (DeepSeek's first dense layer) is unrolled before
    the scan segment.
  * heterogeneous stacks (RecurrentGemma's (rec, rec, attn) pattern) are
    fully unrolled python loops.

Cache layout (see ``kvcache.py``): stacked per layer-kind slot, so scan over
layers zips (stacked params, stacked cache) and emits updated cache — and the
CacheFlow executor can slice per-(layer, token-range) without reshapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import transformer as tfm
from repro.models.kvcache import cache_seq_len, init_cache, layer_slots
from repro.models.layers import (apply_norm, embed_init, init_norm,
                                 sinusoidal_positions)


def _index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


@functools.partial(jax.jit, donate_argnums=0)
def _set_layer(stack, layer, j):
    """Write one layer's params into slot ``j`` of the stacked params, in
    place (the stack is donated)."""
    return jax.tree.map(lambda s, a: s.at[j].set(a), stack, layer)


class Model:
    def __init__(self, cfg: ModelConfig, *, param_dtype=jnp.float32,
                 compute_dtype=jnp.float32, backend: str = "auto",
                 remat_policy: str = "none", moe_groups: int = 0,
                 moe_dropless: bool = True):
        if moe_dropless and cfg.moe is not None and cfg.moe.capacity_factor > 0:
            import dataclasses
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.0))
        self.cfg = cfg
        self.param_dtype = param_dtype
        self.compute_dtype = compute_dtype
        self.backend = backend
        self.remat_policy = remat_policy
        self.moe_groups = moe_groups
        self.slots = layer_slots(cfg)
        # layout: unrolled prefix + scan segment (or fully unrolled)
        if cfg.rglru is not None:
            self.prefix_len = cfg.num_layers          # fully unrolled
        elif cfg.moe is not None and cfg.moe.first_k_dense:
            self.prefix_len = cfg.moe.first_k_dense
        else:
            self.prefix_len = 0
        self.scan_len = cfg.num_layers - self.prefix_len
        # compiled entry points of the serving executor.  Built once per
        # Model (models are cached per config), so every executor shares
        # their compile caches.  The layer index and cache slot are traced:
        # one program serves every layer of a kind at a given chunk shape.
        self._layer_step = jax.jit(self._layer_step_impl,
                                   static_argnames=("kind",))
        self.decode_step_jit = jax.jit(self.decode_step)

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        keys = jax.random.split(rng, cfg.num_layers + 3)
        p: dict = {}
        if cfg.input_mode == "tokens":
            p["embed"] = embed_init(keys[-1], (cfg.vocab_size, cfg.d_model), self.param_dtype)
        if not cfg.tie_embeddings:
            p["unembed"] = embed_init(keys[-2], (cfg.d_model, cfg.vocab_size), self.param_dtype)
        p["final_norm"] = init_norm(cfg.norm, cfg.d_model, self.param_dtype)
        p["prefix_layers"] = [tfm.init_layer(keys[i], cfg, i, self.param_dtype)
                              for i in range(self.prefix_len)]
        if self.scan_len:
            p["scan_layers"] = self._init_scan_layers(keys)
        return p

    def _init_scan_layers(self, keys) -> dict:
        """Stacked params of the scan segment, written layer by layer into
        preallocated arrays: only one layer's params exist beside the stack
        (a list of layers + ``jnp.stack`` would hold every layer twice,
        which does not fit one chip at published widths).  Each layer is
        drawn exactly as before, so the values are bit-identical."""
        cfg, lo = self.cfg, self.prefix_len
        first = tfm.init_layer(keys[lo], cfg, lo, self.param_dtype)
        stack = jax.tree.map(
            lambda a: jnp.zeros((self.scan_len,) + a.shape, a.dtype), first)
        stack = _set_layer(stack, first, 0)
        del first
        for i in range(lo + 1, cfg.num_layers):
            stack = _set_layer(
                stack, tfm.init_layer(keys[i], cfg, i, self.param_dtype),
                i - lo)
        return stack

    def param_specs(self) -> dict:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def num_params(self, params) -> int:
        return sum(int(a.size) for a in jax.tree.leaves(params))

    def layer_params(self, params, i: int):
        if i < self.prefix_len:
            return params["prefix_layers"][i]
        return _index(params["scan_layers"], i - self.prefix_len)

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------
    def embed(self, params, inputs, positions):
        cfg = self.cfg
        if cfg.input_mode == "tokens":
            x = params["embed"].astype(self.compute_dtype)[inputs]
        else:
            x = inputs.astype(self.compute_dtype)
        if cfg.position == "sinusoidal":
            x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
        return x

    def unembed(self, params, x):
        cfg = self.cfg
        x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        table = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
        return x @ table.astype(x.dtype)

    # ------------------------------------------------------------------
    # Full forward (train) / prefill
    # ------------------------------------------------------------------
    def _layer_full(self, p, cfg_kind, x, positions, states):
        """One layer, full sequence. states: per-kind state views or None.

        Layer boundaries carry explicit sharding constraints: batch over
        ("pod","data") and — sequence-parallel, Megatron-SP style — sequence
        over "model".  This pins GSPMD to gathering *weights* per layer (the
        FSDP/2D-TP intent) instead of replicating activations, and shrinks
        remat-saved activations by the TP degree.  No-ops off-mesh.
        """
        from repro.distributed.constraints import constrain
        cfg = self.cfg
        if cfg_kind == "attention":
            x, entry, aux = tfm.attention_layer_full(
                cfg, p, x, positions, backend=self.backend, moe_groups=self.moe_groups)
            entry = {f: constrain(a, ("pod", "data"), "model")
                     for f, a in entry.items()}
            return constrain(x, ("pod", "data"), "model", None), entry, aux
        if cfg_kind == "recurrent":
            conv, h0 = states
            x, conv, h = tfm.recurrent_layer_full(cfg, p, x, conv, h0, backend=self.backend)
            return (constrain(x, ("pod", "data"), "model", None), (conv, h),
                    jnp.zeros((), jnp.float32))
        if cfg_kind == "rwkv":
            stm, scm, wkv = states
            x, stm, scm, wkv = tfm.rwkv_layer_full(cfg, p, x, stm, scm, wkv,
                                                   backend=self.backend)
            return (constrain(x, ("pod", "data"), "model", None),
                    (stm, scm, wkv), jnp.zeros((), jnp.float32))
        raise ValueError(cfg_kind)

    def fresh_state(self, kind: str, b: int, dtype):
        cfg = self.cfg
        if kind == "recurrent":
            w = cfg.rglru.lru_width or cfg.d_model
            return (jnp.zeros((b, cfg.rglru.conv1d_width - 1, w), dtype),
                    jnp.zeros((b, w), jnp.float32))
        if kind == "rwkv":
            h = cfg.d_model // cfg.rwkv.head_size
            return (jnp.zeros((b, cfg.d_model), dtype),
                    jnp.zeros((b, cfg.d_model), dtype),
                    jnp.zeros((b, h, cfg.rwkv.head_size, cfg.rwkv.head_size), jnp.float32))
        return None

    def run_layer_full(self, params, i: int, x, positions, states=None):
        """One layer, full-sequence mode. Returns (x', cache_entry_or_state).
        Used by the layer-wise restoration executor (bottom-up forward)."""
        kind = self.cfg.layer_kinds()[i]
        if states is None:
            states = self.fresh_state(kind, x.shape[0], x.dtype)
        return self._layer_full(self.layer_params(params, i), kind, x, positions,
                                states)[:2]

    def layer_chunk(self, params, i: int, x, positions, cache):
        """One layer over a chunk, attending to + updating the cache
        (compiled; see ``_layer_step``)."""
        kind, slot = self.slots[i]
        if i < self.prefix_len:
            return self._layer_step(params["prefix_layers"][i], None, x,
                                    positions, dict(cache), slot, kind=kind)
        return self._layer_step(params["scan_layers"], i - self.prefix_len,
                                x, positions, dict(cache), slot, kind=kind)

    def _layer_step_impl(self, layer_params, j, x, positions, cache, slot, *,
                         kind):
        """``layer_params`` is one layer's params (``j`` None) or the
        stacked scan segment, of which layer ``j`` runs."""
        if j is not None:
            layer_params = _index(layer_params, j)
        return self._layer_cached(layer_params, kind, slot, x, positions,
                                  cache)

    def forward(self, params, inputs, positions=None, collect_cache: bool = False):
        """Whole-sequence forward.

        Returns (logits, aux) or (logits, aux, raw_entries) when
        ``collect_cache`` — raw_entries are full-sequence per-layer cache
        entries (list in layer order) for cache construction.
        """
        cfg = self.cfg
        b, s = inputs.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = self.embed(params, inputs, positions)
        aux_total = jnp.zeros((), jnp.float32)
        entries = []
        kinds = cfg.layer_kinds()

        # fresh zero states for recurrent/rwkv kinds
        def fresh_state(kind):
            return self.fresh_state(kind, b, x.dtype)

        for i in range(self.prefix_len):
            x, entry, aux = self._layer_full(params["prefix_layers"][i], kinds[i], x,
                                             positions, fresh_state(kinds[i]))
            aux_total += aux
            entries.append(entry)

        if self.scan_len:
            kind = kinds[self.prefix_len]          # scan segment is uniform

            def body(carry, layer_p):
                xc, auxc = carry
                xc, entry, aux = self._layer_full(layer_p, kind, xc, positions,
                                                  fresh_state(kind))
                out = entry if (collect_cache or kind != "attention") else 0.0
                return (xc, auxc + aux), out

            if self.remat_policy != "none":
                body = _remat(body, self.remat_policy)
            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), params["scan_layers"])
            if collect_cache or kind != "attention":
                entries.append(("scan", ys))

        logits = self.unembed(params, x)
        if collect_cache:
            return logits, aux_total, entries
        return logits, aux_total

    # ------------------------------------------------------------------
    # Prefill: full forward + cache construction
    # ------------------------------------------------------------------
    def prefill(self, params, inputs, positions=None):
        """Returns (last-token logits (B,V), cache filled with the sequence)."""
        cfg = self.cfg
        b, s = inputs.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        logits, _, entries = self.forward(params, inputs, positions, collect_cache=True)
        cache = self._entries_to_cache(entries, positions, s)
        return logits[:, -1], cache

    def _entries_to_cache(self, entries, positions, s, max_len: Optional[int] = None):
        """Assemble the stacked cache dict from per-layer full-sequence entries."""
        cfg = self.cfg
        s_cache = cache_seq_len(cfg, max_len or s)
        cache: dict = {}
        kinds = cfg.layer_kinds()

        # unpack scan ys back into per-layer entries
        flat: list = []
        for e in entries:
            if isinstance(e, tuple) and len(e) == 2 and e[0] == "scan":
                ys = e[1]
                for j in range(self.scan_len):
                    flat.append(_index(ys, j))
            else:
                flat.append(e)

        attn_entries = [e for e, k in zip(flat, kinds) if k == "attention"]
        rec_entries = [e for e, k in zip(flat, kinds) if k == "recurrent"]
        rwkv_entries = [e for e, k in zip(flat, kinds) if k == "rwkv"]

        pos_row = positions[0]
        if attn_entries:
            if cfg.attn_window and s > s_cache:
                sel = jnp.arange(s - s_cache, s)
                slot = pos_row[sel] % s_cache
            else:
                sel = jnp.arange(s)
                slot = pos_row % s_cache

            def to_cache(seq_arr):
                tail = seq_arr[:, sel]
                buf_shape = (seq_arr.shape[0], s_cache) + seq_arr.shape[2:]
                buf = jnp.zeros(buf_shape, seq_arr.dtype)
                return buf.at[:, slot].set(tail)

            if cfg.mla is not None:
                cache["ckv"] = jnp.stack([to_cache(e["ckv"]) for e in attn_entries])
            else:
                cache["k"] = jnp.stack([to_cache(e["k"]) for e in attn_entries])
                cache["v"] = jnp.stack([to_cache(e["v"]) for e in attn_entries])
            kpos_row = jnp.full((s_cache,), -1, jnp.int32).at[slot].set(pos_row[sel])
            cache["kpos"] = jnp.broadcast_to(kpos_row[None], (len(attn_entries), s_cache))
        if rec_entries:
            cache["conv"] = jnp.stack([e[0] for e in rec_entries])
            cache["lru"] = jnp.stack([e[1] for e in rec_entries])
        if rwkv_entries:
            cache["shift_tm"] = jnp.stack([e[0] for e in rwkv_entries])
            cache["shift_cm"] = jnp.stack([e[1] for e in rwkv_entries])
            cache["wkv"] = jnp.stack([e[2] for e in rwkv_entries])
        return cache

    # ------------------------------------------------------------------
    # Cached-chunk forward (decode C=1; restoration chunks C>1)
    # ------------------------------------------------------------------
    def _layer_cached(self, p, kind, slot, x, positions, cache):
        cfg = self.cfg
        if kind == "attention":
            if cfg.mla is not None:
                view = {"ckv": cache["ckv"][slot], "kpos": cache["kpos"][slot]}
            else:
                view = {"k": cache["k"][slot], "v": cache["v"][slot],
                        "kpos": cache["kpos"][slot]}
            x, new = tfm.attention_layer_cached(cfg, p, x, positions, view,
                                                backend=self.backend,
                                                moe_groups=self.moe_groups)
            for f, a in new.items():
                cache[f] = cache[f].at[slot].set(a)
            return x, cache
        if kind == "recurrent":
            x, conv, h = tfm.recurrent_layer_full(cfg, p, x, cache["conv"][slot],
                                                  cache["lru"][slot], backend=self.backend)
            cache["conv"] = cache["conv"].at[slot].set(conv)
            cache["lru"] = cache["lru"].at[slot].set(h)
            return x, cache
        if kind == "rwkv":
            x, stm, scm, wkv = tfm.rwkv_layer_full(cfg, p, x, cache["shift_tm"][slot],
                                                   cache["shift_cm"][slot],
                                                   cache["wkv"][slot], backend=self.backend)
            cache["shift_tm"] = cache["shift_tm"].at[slot].set(stm)
            cache["shift_cm"] = cache["shift_cm"].at[slot].set(scm)
            cache["wkv"] = cache["wkv"].at[slot].set(wkv)
            return x, cache
        raise ValueError(kind)

    def stack_chunk(self, params, x, positions, cache, lo: int = 0, hi: Optional[int] = None):
        """Run layers [lo, hi) over a chunk (B,C,D), attending to + updating
        the cache. The workhorse of token-wise and stage-local restoration."""
        cfg = self.cfg
        hi = cfg.num_layers if hi is None else hi
        # scan fast-path: whole stack of a uniform arch
        if cfg.is_uniform and lo == 0 and hi == cfg.num_layers and self.scan_len:
            kind = cfg.layer_kinds()[0]

            def body(xc, xs):
                layer_p, layer_cache = xs
                if kind == "attention":
                    xc, new = tfm.attention_layer_cached(
                        cfg, layer_p, xc, positions, layer_cache,
                        backend=self.backend, moe_groups=self.moe_groups)
                    return xc, new
                elif kind == "rwkv":
                    xc, stm, scm, wkv = tfm.rwkv_layer_full(
                        cfg, layer_p, xc, layer_cache["shift_tm"],
                        layer_cache["shift_cm"], layer_cache["wkv"],
                        backend=self.backend)
                    return xc, {"shift_tm": stm, "shift_cm": scm, "wkv": wkv}
                raise ValueError(kind)

            x, new_cache = jax.lax.scan(body, x, (params["scan_layers"], cache))
            return x, new_cache

        for i in range(lo, hi):
            x, cache = self.layer_chunk(params, i, x, positions, cache)
        return x, cache

    def decode_step_append(self, params, tokens, cache, tail, tail_len, pos):
        """Append-buffer decode (beyond-paper optimisation, EXPERIMENTS.md
        §Perf): the big prefix cache is READ-ONLY; the new token's KV is
        written into a small ``tail`` buffer instead, and attention runs over
        [cache || tail].  This removes the masked full-cache writes GSPMD
        emits for dynamic updates into a sequence-sharded cache — the engine
        merges tails back every W steps, off the decode critical path.

        tail: cache-shaped dict with S = W slots; tail_len: scalar i32.
        Returns (logits, tail')."""
        cfg = self.cfg
        assert cfg.is_uniform and self.scan_len, \
            "append-buffer decode requires a uniform scan stack"
        b = tokens.shape[0]
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b, 1))
        inp = tokens[:, None] if cfg.input_mode == "tokens" else tokens[:, None, :]
        x = self.embed(params, inp, positions)
        x, new_tail = self._decode_append_scan(params, x, positions, cache,
                                               tail, tail_len)
        logits = self.unembed(params, x)
        return logits[:, 0], new_tail

    def _decode_append_scan(self, params, x, positions, cache, tail, tail_len):
        cfg = self.cfg

        def body(xc, xs):
            layer_p, layer_cache, layer_tail = xs
            from repro.models import attention as attn_mod
            from repro.models import mla as mla_mod
            from repro.models.layers import apply_norm
            from repro.models import transformer as tfm_mod
            h = apply_norm(cfg.norm, layer_p["norm1"], xc, cfg.norm_eps)
            if cfg.mla is not None:
                q_nope, q_rope = mla_mod._project_q(cfg, layer_p["attn"], h,
                                                    positions)
                ckv_new = mla_mod.compress_kv(cfg, layer_p["attn"], h, positions)
                lt = dict(layer_tail)
                lt["ckv"] = jax.lax.dynamic_update_slice_in_dim(
                    layer_tail["ckv"], ckv_new.astype(layer_tail["ckv"].dtype),
                    tail_len, axis=1)
                lt["kpos"] = jax.lax.dynamic_update_slice_in_dim(
                    layer_tail["kpos"], positions[0], tail_len, axis=0)
                full_ckv = jnp.concatenate([layer_cache["ckv"], lt["ckv"]], axis=1)
                kp = jnp.concatenate([layer_cache["kpos"], lt["kpos"]])
                a = mla_mod.mla_attend_absorbed(
                    cfg, layer_p["attn"], q_nope, q_rope, positions,
                    full_ckv.astype(h.dtype), kp)
            else:
                q, k_new, v_new = attn_mod._project_qkv(cfg, layer_p["attn"], h,
                                                        positions)
                lt = dict(layer_tail)
                lt["k"] = jax.lax.dynamic_update_slice_in_dim(
                    layer_tail["k"], k_new.astype(layer_tail["k"].dtype),
                    tail_len, axis=1)
                lt["v"] = jax.lax.dynamic_update_slice_in_dim(
                    layer_tail["v"], v_new.astype(layer_tail["v"].dtype),
                    tail_len, axis=1)
                lt["kpos"] = jax.lax.dynamic_update_slice_in_dim(
                    layer_tail["kpos"], positions[0], tail_len, axis=0)
                scale = 1.0 / (cfg.qk_head_dim ** 0.5)
                from repro.distributed.collectives import lse_decode_attention
                from repro.distributed.constraints import _ambient_mesh
                mesh = _ambient_mesh()
                seq_sharded = (mesh is not None
                               and mesh.shape.get("model", 1) > 1
                               and cfg.num_kv_heads % mesh.shape["model"] != 0)
                if seq_sharded:
                    # sequence-sharded cache: LSE-combine partial attention;
                    # comm = (B,Hq,Dh) psum, NOT a full-cache all-gather, and
                    # the tail merges inside the shard (no cache reshard)
                    a = lse_decode_attention(
                        q, layer_cache["k"].astype(q.dtype),
                        layer_cache["v"].astype(q.dtype), layer_cache["kpos"],
                        positions, scale=scale, window=cfg.attn_window,
                        tail=(lt["k"], lt["v"], lt["kpos"]))
                else:
                    k_full = jnp.concatenate([layer_cache["k"], lt["k"]], axis=1)
                    v_full = jnp.concatenate([layer_cache["v"], lt["v"]], axis=1)
                    kp = jnp.concatenate([layer_cache["kpos"], lt["kpos"]])
                    kpb = jnp.broadcast_to(kp[None], (q.shape[0], kp.shape[0]))
                    a = attn_mod._gqa_flash(q, k_full.astype(q.dtype),
                                            v_full.astype(q.dtype),
                                            positions, kpb, scale, cfg.attn_window)
                a = a.reshape(*h.shape[:2], cfg.num_heads * cfg.head_dim)
                a = a @ layer_p["attn"]["wo"].astype(h.dtype)
            xc = xc + a
            h = apply_norm(cfg.norm, layer_p["norm2"], xc, cfg.norm_eps)
            f, _ = tfm_mod._ffn(cfg, layer_p, h, self.moe_groups)
            return xc + f, lt

        sub_cache = {f: cache[f] for f in ("k", "v", "ckv", "kpos") if f in cache}
        x, new_tail = jax.lax.scan(body, x, (params["scan_layers"], sub_cache, tail))
        return x, new_tail

    def init_tail(self, batch: int, window: int, dtype=None):
        """Small append buffer for decode_step_append."""
        cfg = self.cfg
        t = init_cache(cfg, batch, window, dtype or self.compute_dtype)
        return t

    def decode_step(self, params, tokens, cache, pos):
        """tokens: (B,) int32 (or (B,D) embeddings); pos: scalar int32.
        Returns (logits (B,V), cache')."""
        cfg = self.cfg
        b = tokens.shape[0]
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b, 1))
        inp = tokens[:, None] if cfg.input_mode == "tokens" else tokens[:, None, :]
        x = self.embed(params, inp, positions)
        x, cache = self.stack_chunk(params, x, positions, cache)
        logits = self.unembed(params, x)
        return logits[:, 0], cache

    def prefill_chunk(self, params, inputs, cache, start_pos):
        """Chunk prefill against an existing cache (token-wise restoration
        recompute step): inputs (B,C); start_pos scalar. Returns
        (last logits, cache')."""
        cfg = self.cfg
        b, c = inputs.shape[:2]
        positions = start_pos + jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None],
                                                 (b, c))
        x = self.embed(params, inputs, positions)
        x, cache = self.stack_chunk(params, x, positions, cache)
        logits = self.unembed(params, x[:, -1:])
        return logits[:, 0], cache

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None):
        return init_cache(self.cfg, batch, max_len,
                          dtype or self.compute_dtype)


def _remat(fn, policy: str):
    policies = {
        "full": None,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "nothing": jax.checkpoint_policies.nothing_saveable,
    }
    pol = policies.get(policy)
    if policy == "full" or pol is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=pol)


@functools.lru_cache(maxsize=None)
def _cached_model(cfg: ModelConfig, param_dtype_name: str, compute_dtype_name: str,
                  backend: str, remat_policy: str, moe_groups: int,
                  moe_dropless: bool) -> Model:
    import numpy as np
    return Model(cfg, param_dtype=np.dtype(param_dtype_name),
                 compute_dtype=np.dtype(compute_dtype_name), backend=backend,
                 remat_policy=remat_policy, moe_groups=moe_groups,
                 moe_dropless=moe_dropless)


def build_model(cfg: ModelConfig, *, param_dtype=jnp.float32, compute_dtype=jnp.float32,
                backend: str = "auto", remat_policy: str = "none",
                moe_groups: int = 0, moe_dropless: bool = True) -> Model:
    import numpy as np
    return _cached_model(cfg, np.dtype(param_dtype).name, np.dtype(compute_dtype).name,
                         backend, remat_policy, moe_groups, moe_dropless)
