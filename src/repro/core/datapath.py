"""The fused restoration data path: real per-channel transfer streams
feeding one dequant-scatter kernel launch per load op.

The engine core schedules restoration as ``(layer-span, token-range)``
I/O units over ``io_channels`` — historically a pure contention model.
This module is the execution substrate behind it:

  * :class:`TransferStream` — one host→device staging queue per channel
    (pinned to a physical mesh device by
    ``distributed.sharding.io_channel_devices``).  A channel whose device
    does not hold the live cache hands its staged bytes to the cache's
    device by an explicit copy before the scatter.  ``put`` issues an
    *asynchronous* ``jax.device_put`` and only blocks on the oldest
    in-flight buffer beyond ``depth``: with the default depth of 2, op
    k+1's host→device copy is in flight while op k's dequant-scatter
    kernel still consumes its buffer (double buffering), and the
    backpressure bounds staging memory to ``depth`` op payloads per
    channel.
  * :class:`RestoreDatapath` — executes one load op's data movement.
    The op's chunks (in *stored* encoding, via
    ``ChunkStore.fetch_range_packed``) are grouped into contiguous
    same-residency runs; each transfer run is packed into ONE multi-chunk
    staging buffer per field (int8 bytes + per-chunk scales cross the
    wire — half the fp16 bytes), staged through the channel's stream, and
    scattered into the live cache by ONE fused
    :func:`~repro.kernels.kv_restore.kv_restore_scatter` launch.  Runs
    already HBM-resident copy device-to-device from the pool views.  Each
    transferred chunk then lands its pool block via
    ``ChunkStore.promote_staged`` — built from the bytes already on
    device, so nothing crosses the wire twice.

Invariants the quantized path preserves (tested):

  * the on-device dequant is bit-identical to ``kv_dequantize``'s f32
    multiply + single cast, so fused and legacy restores agree within
    ``quant_tolerance()`` (and bit-exactly for ``quant="none"``);
  * store accounting (``bytes_transferred`` / ``fetches`` / ``io_hits``)
    is byte-identical to the legacy per-chunk ``fetch`` path;
  * staging buffers are zero-padded to whole chunks; padded rows fall
    past the cache's token extent and are clipped by the scatter.

In measured mode (``measure=True``, i.e. ``RealBackend`` without a
duration model) each op blocks on its written cache fields and the wall
seconds + wire bytes are attributed to the op's channel —
``RealBackend.io_secs`` charges the engine clock with the measured
transfer time and per-channel bandwidth becomes an observable.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.kv_restore import kv_restore_scatter
from repro.kernels.kv_restore.ops import pick_backend
from repro.obs.spans import span

ATTN_FIELDS = ("k", "v", "ckv")


class TransferStream:
    """One host→device staging queue — an engine I/O channel made real."""

    def __init__(self, device=None, *, depth: int = 2):
        self.device = device
        self.depth = max(1, int(depth))
        self._inflight: deque = deque()
        self.puts = 0                  # staged host→device copies issued
        self.bytes_staged = 0          # bytes handed to device_put
        self.secs = 0.0                # measured wall secs (measure mode)
        self.bytes_moved = 0           # wire bytes behind those secs

    def put(self, host: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        """Stage one op's packed buffers toward this stream's device.
        Asynchronous: returns immediately-usable (lazy) device arrays and
        only synchronizes on the oldest in-flight put beyond ``depth``."""
        while len(self._inflight) >= self.depth:
            jax.block_until_ready(self._inflight.popleft())
        if self.device is not None:
            dev = {k: jax.device_put(v, self.device) for k, v in host.items()}
        else:
            dev = {k: jnp.asarray(v) for k, v in host.items()}
        self._inflight.append(list(dev.values()))
        self.puts += 1
        self.bytes_staged += sum(int(v.nbytes) for v in host.values())
        return dev

    def note(self, secs: float, nbytes: int):
        self.secs += secs
        self.bytes_moved += nbytes

    def bandwidth(self) -> Optional[float]:
        """Measured bytes/sec over everything attributed to this channel
        (None until the first measured transfer)."""
        return self.bytes_moved / self.secs if self.secs > 0 else None

    def sync(self):
        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())


def _split_runs(packed) -> List[list]:
    """Group an op's chunks into maximal contiguous runs of equal
    residency (resident pool views vs. bytes that must cross the wire) —
    one scatter per run keeps the kernel's token range contiguous."""
    runs: List[list] = []
    prev_cat, prev_c1 = None, None
    for item in packed:
        c0, _c1, form = item[0], item[1], item[2]
        cat = "hbm" if form == "hbm" else "xfer"
        if runs and cat == prev_cat and c0 == prev_c1:
            runs[-1].append(item)
        else:
            runs.append([item])
        prev_cat, prev_c1 = cat, item[1]
    return runs


class RestoreDatapath:
    """Per-channel double-buffered fetch→dequant→scatter pipeline."""

    def __init__(self, streams: Optional[Sequence[TransferStream]] = None,
                 *, backend: str = "auto", depth: int = 2,
                 measure: bool = False):
        self.streams = list(streams) if streams else [TransferStream(
            depth=depth)]
        self.backend = backend
        self.measure = measure
        # staged runs, by what scattered them: the Pallas kernel, or the
        # jitted oracle (off TPU, or unaligned shapes on TPU)
        self.pallas_launches = 0
        self.oracle_runs = 0
        self.resident_copies = 0       # device-local copies of HBM runs
        # staged runs whose channel device is not the cache's: the bytes
        # move chip to chip explicitly before the scatter
        self.device_moves = 0
        self.device_move_bytes = 0
        self.runs = 0
        self.ops = 0
        self.last_op_dispatches = 0    # copy dispatches of the latest op
        self.last_op_bytes = 0         # wire bytes staged by the latest op
        self._last_secs: Optional[float] = None

    @classmethod
    def for_channels(cls, io_channels: Optional[int] = None, mesh=None, *,
                     backend: str = "auto", depth: int = 2):
        """One stream per engine I/O channel, pinned round-robin onto the
        mesh's physical devices (every device gets its own fetch queue on
        a real sharded deployment)."""
        from repro.distributed.sharding import io_channel_devices
        devs = io_channel_devices(mesh, io_channels)
        return cls([TransferStream(d, depth=depth) for d in devs],
                   backend=backend)

    def stream_for(self, channel: int) -> TransferStream:
        return self.streams[channel % len(self.streams)]

    def bandwidths(self) -> List[Optional[float]]:
        return [s.bandwidth() for s in self.streams]

    def pop_measured_secs(self) -> Optional[float]:
        secs, self._last_secs = self._last_secs, None
        return secs

    # ------------------------------------------------------------------
    def restore_op(self, cache: dict, packed, *, store, slot_span,
                   channel: int = 0) -> dict:
        """Execute one load op's data movement into the live ``cache``
        (mutated in place and returned).  ``packed`` is the op's
        ``fetch_range_packed`` result; ``slot_span`` the contiguous
        attention-slot range the op's layer span owns."""
        with span("load.scatter") as sp:
            self._restore_op(cache, packed, store, slot_span, channel)
            sp.set(bytes_staged=self.last_op_bytes)
        return cache

    def _restore_op(self, cache: dict, packed, store, slot_span,
                    channel: int):
        fields = [f for f in ATTN_FIELDS if f in cache]
        s_lo, s_hi = slot_span
        cs = store.chunk_size
        stream = self.stream_for(channel)
        a = cache["kpos"].shape[0]
        s = cache[fields[0]].shape[2]
        assert cache[fields[0]].shape[1] == 1, "datapath assumes B == 1"
        dispatches = 0
        moved = 0
        t_begin = time.perf_counter() if self.measure else 0.0

        for run in _split_runs(packed):
            r0, r1 = run[0][0], run[-1][1]
            form = run[0][2]
            if form == "hbm":
                staged, kpos_dev = self._gather_resident(run, fields)
                scales_dev = None
                self.resident_copies += 1
            else:
                host, nbytes = self._pack_host(run, fields, cs, a)
                dev = self._to_cache_device(stream.put(host), cache, nbytes)
                dispatches += 1                    # one staged copy per run
                moved += nbytes
                staged = {f: dev[f] for f in fields}
                kpos_dev = dev["kpos"]
                scales_dev = ({f: dev[f + "__s"] for f in fields}
                              if form == "int8" else None)

            # one fused (dequantizing) scatter per run, all fields in the
            # launch; resident runs are device-local copies and take the
            # jitted oracle (XLA fuses them into one update per field)
            views = [cache[f].reshape(a, s, -1) for f in fields]
            staged_l = [staged[f] for f in fields]
            backend = "ref"
            if form != "hbm":
                backend = pick_backend(views, staged_l, t0=r0, chunk_size=cs,
                                       backend=self.backend)
                if backend == "ref":
                    self.oracle_runs += 1
                else:
                    self.pallas_launches += 1
            out = kv_restore_scatter(
                views, staged_l,
                None if scales_dev is None else [scales_dev[f]
                                                 for f in fields],
                t0=r0, slot_lo=s_lo, n_slots=s_hi - s_lo, chunk_size=cs,
                backend=backend)
            for f, o in zip(fields, out):
                cache[f] = o.reshape(cache[f].shape)
            dispatches += 1
            # one kpos update per RUN, not per chunk x layer x field —
            # already amortized by the run split
            cache["kpos"] = cache["kpos"].at[s_lo:s_hi, r0:r1].set(  # codelint: allow(at-set-loop)
                kpos_dev[s_lo:s_hi])
            dispatches += 1

            if form != "hbm":
                self._promote_run(run, fields, cache, staged, scales_dev,
                                  kpos_dev, store)
            self.runs += 1

        self.ops += 1
        self.last_op_dispatches = dispatches
        self.last_op_bytes = moved
        if self.measure:
            jax.block_until_ready([cache[f] for f in fields]
                                  + [cache["kpos"]])
            secs = time.perf_counter() - t_begin
            stream.note(secs, moved)
            self._last_secs = secs

    def _to_cache_device(self, dev: dict, cache: dict, nbytes: int) -> dict:
        """Staged buffers land on their channel's device; the scatter runs
        where the live cache is.  A channel pinned to another chip hands
        its bytes over by an explicit device-to-device copy (ICI on a
        multi-chip host) — never by letting the scatter follow the staged
        arrays off the cache's chip."""
        target = next(iter(cache["kpos"].devices()))
        src = next(iter(next(iter(dev.values())).devices()))
        if src == target:
            return dev
        self.device_moves += 1
        self.device_move_bytes += nbytes
        return jax.device_put(dev, target)

    # ------------------------------------------------------------------
    @staticmethod
    def _gather_resident(run, fields):
        """Concatenate a resident run's pool views into (A, T, C) staging
        shapes — device-to-device, nothing crosses the wire."""
        staged = {}
        for f in fields:
            parts = [jnp.asarray(item[3][f]) for item in run]
            cat = parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=2)
            staged[f] = cat.reshape(cat.shape[0], cat.shape[2], -1)
        kpos = (jnp.asarray(run[0][3]["kpos"]) if len(run) == 1
                else jnp.concatenate([jnp.asarray(item[3]["kpos"])
                                      for item in run], axis=1))
        return staged, kpos

    @staticmethod
    def _pack_host(run, fields, cs, a):
        """Pack a transfer run's stored chunk payloads into one staging
        buffer per field: (A, n_chunks·cs, C) with zero-padded tails, plus
        per-chunk per-channel scales (n_chunks, C) on the int8 path and
        the run's kpos rows.  Returns (host dict, wire bytes)."""
        quant = run[0][2] == "int8"
        host = {"kpos": np.concatenate([np.asarray(item[3]["kpos"])
                                        for item in run], axis=1)}
        nbytes = host["kpos"].nbytes
        for f in fields:
            parts, scl = [], []
            for c0, c1, _form, pay, _key in run:
                rep = pay[f]
                arr = np.asarray(rep["q"] if quant else rep)
                assert arr.shape[1] == 1, "datapath assumes B == 1"
                a3 = arr.reshape(a, c1 - c0, -1)
                if c1 - c0 < cs:
                    a3 = np.concatenate(
                        [a3, np.zeros((a, cs - (c1 - c0), a3.shape[2]),
                                      a3.dtype)], axis=1)
                parts.append(a3)
                if quant:
                    sc = np.asarray(rep["scales"], np.float32)
                    scl.append(np.tile(sc, a3.shape[2] // sc.shape[0]))
                    nbytes += sc.nbytes
                nbytes += arr.nbytes
            host[f] = np.concatenate(parts, axis=1)
            if quant:
                host[f + "__s"] = np.stack(scl)
        return host, nbytes

    @staticmethod
    def _promote_run(run, fields, cache, staged, scales_dev, kpos_dev,
                     store):
        """Land each transferred chunk's pool block from the staged device
        bytes (dequantized on device for int8, bit-identically to the
        scatter kernel's math) — the store's HBM promote then consumes
        these instead of a second host→device copy."""
        r0 = run[0][0]
        a = cache["kpos"].shape[0]
        for idx, (c0, c1, _form, _pay, key) in enumerate(run):
            off, n = c0 - r0, c1 - c0
            dev = {"kpos": kpos_dev[:, off:off + n]}
            for f in fields:
                sl = staged[f][:, off:off + n]
                if scales_dev is not None:
                    sl = (sl.astype(jnp.float32)
                          * scales_dev[f][idx]).astype(cache[f].dtype)
                dev[f] = sl.reshape((a, 1, n) + cache[f].shape[3:])
            store.promote_staged(key, dev)
