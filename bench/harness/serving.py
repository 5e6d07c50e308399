"""The system under test, driven through its served path.

``RealServingEngine.serve`` → ``EngineCore`` → ``RestorationExecutor`` →
``ChunkStore`` / ``RestoreDatapath`` → ``Model``.  The harness adds only
the inputs: the engine's own input source draws every request from one
fixed key, so :class:`BenchEngine` hands it the traffic's suffix tokens,
in the order ``serve`` asks for them.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic
from harness.arch import Arch, family_of


def build_model(a: Arch, params: dict):
    """The program's model for ``a``; checks that ``params`` has exactly
    the tree, shapes and dtypes the program's own init would make."""
    from repro.models.model import build_model as program_build
    dt = jnp.dtype(a.dtype)
    model = program_build(family_of(a).program_config(a), param_dtype=dt,
                          compute_dtype=dt)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree for {a.name}")
    return model


def _engine_class():
    from repro.serving.engine import RealServingEngine

    class BenchEngine(RealServingEngine):
        """The served engine with the traffic's suffixes as its inputs."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.suffixes: deque = deque()

        def _inputs(self, n: int):
            tokens = self.suffixes.popleft()
            if tokens.shape[0] != n:
                raise RuntimeError(f"engine asked for {n} suffix tokens, the "
                                   f"traffic queued {tokens.shape[0]}")
            return jnp.asarray(tokens)[None]

    return BenchEngine


def build_engine(a: Arch, model, params, store_cfg: dict):
    from repro.storage.chunkstore import ChunkStore
    store = ChunkStore(chunk_size=a.chunk_size, quant=store_cfg["quant"],
                       default_tier=store_cfg["tier"],
                       hbm_cap=a.hbm_tier_bytes,
                       host_cap=int(store_cfg["host_bytes"]))
    return _engine_class()(model, params, system="cacheflow",
                           stages=a.stages, chunk_size=a.chunk_size,
                           max_batch=a.max_batch, io_channels=1,
                           kvstore=store, datapath="fused")


def remember_pool(engine, pool: List[traffic.Session], seed: int, vocab: int):
    """The previous turn of every session: chunked prefill, stored in the
    chunk store's default tier."""
    for s in pool:
        toks = traffic.prefix_tokens(seed, s, vocab)
        engine.executor.remember(s.sid, jnp.asarray(toks)[None])
    jax.block_until_ready(jax.tree.leaves(
        [engine.executor.store.get(s.sid).kv_reference for s in pool]))


@dataclass
class TurnResult:
    sid: str
    prefix: int
    suffix: int
    outputs: int
    suffix_tokens: np.ndarray
    served: List[int]
    logits: np.ndarray                 # (outputs, vocab) the program's, bf16
    ttft_s: float
    gaps_s: List[float]
    failed: bool


def serve_wave(engine, probe, turns: List[traffic.Turn]
               ) -> List[TurnResult]:
    """All of a wave's turns arrive at once; serve them, read back what the
    correctness check needs, and drop each turn's live state (its stored
    chunks stay, so the session can return)."""
    from repro.serving.request import Request
    reqs = [Request(t.sid, 0.0, prefix_len=t.prefix, new_len=t.suffix,
                    decode_len=t.outputs) for t in turns]
    engine.suffixes.extend(t.suffix_tokens for t in turns)
    n_ops = len(probe.rec.ops)
    with probe.span("wave"):
        t0 = time.perf_counter()
        engine.serve(reqs, verify=False)
    ex = engine.executor
    bad = {s.rid for s in probe.rec.ops[n_ops:]
           if s.kind == "load" and (s.from_reference or (
               s.oracle_runs and probe.require_pallas))}
    out = []
    for t in turns:
        o = ex.outputs(t.sid)
        served = o["tokens"]
        rows = [o["first_logits"]] + o["step_logits"]
        times = probe.token_times.pop(t.sid, [])
        first = probe.first_token.pop(t.sid)
        out.append(TurnResult(
            t.sid, t.prefix, t.suffix, t.outputs, t.suffix_tokens,
            [int(x) for x in served],
            np.concatenate([np.asarray(x) for x in rows]), first - t0,
            [b - a for a, b in zip(times, times[1:])],
            t.sid in bad or len(served) != t.outputs))
        ex.drop_restore(t.sid)
    return out


def pool_dedup_hits(engine) -> int:
    return engine.executor.chunk_store.dedup_hits


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def free_engine(engine, probe):
    """Drop every reference the harness holds into the program."""
    probe.detach()
    ex = engine.executor
    for rid in list(ex._live):
        ex.drop_restore(rid)
    engine.__dict__.clear()
