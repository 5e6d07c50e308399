#!/usr/bin/env python3
"""Bring-up check: serve Qwen3-8B widths through the real restoration path
on a TPU.

    python chip_smoke.py               # one chip: phases A, B, warm A
    python chip_smoke.py --four-chips  # four-chip host: --io-channels 4 vs 1

Everything runs in this one process, which holds the chip.  The model is
Qwen3-8B at its published widths (d_model 4096, 32/8 heads x 128, d_ff
12288, vocab 151936, QK-norm), cut to 18 of 36 layers, bfloat16 weights
drawn from a seed.  Requests go through the function ``serve --real``
calls (``repro.launch.serve.serve_real``): RealServingEngine -> EngineCore
-> RestorationExecutor -> ChunkStore / RestoreDatapath, and every restored
cache is verified against its full-prefill reference.

One chip:
  A       4 requests x 2048-token prefixes, 64 new tokens, 8 output tokens,
          max batch 2, 2 stages, KV stored unquantized in the host tier;
  B       the same requests with int8 KV in the remote (disk) tier, which
          runs kv_quant on demotion and the int8 dequant-scatter;
  A-warm  phase A again with fresh request ids, after compilation.
Four chips: phase A with one restoration channel per chip, then with one
channel; both must give identical restored caches and greedy tokens.

Each phase prints one JSON line.  Times there are bring-up output, not
measurements.  The last line is ``{"ok": true, "device": {...}}``; any
failed check exits non-zero with a message instead.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Optional

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


@dataclasses.dataclass(frozen=True)
class Shape:
    arch: str = "qwen3-8b"
    layers: Optional[int] = 18       # None: the config's .reduced() model
    dtype: str = "bfloat16"
    requests: int = 4
    prefix_len: int = 2048
    new_len: int = 64
    decode_len: int = 8
    chunk_size: int = 128
    max_batch: int = 2
    stages: int = 2
    seed: int = 0


def _peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_phase(name: str, model, params, shape: Shape, *, kv_quant: str,
              kv_tier: str, io_channels: int = 1, id_prefix: str = "r",
              require_pallas: bool = True, keep_caches: bool = False):
    """Serve one phase; check it; return (printed record, greedy tokens,
    restored caches on the host when ``keep_caches``)."""
    import jax
    import numpy as np
    from repro.launch.serve import real_requests, serve_real
    reqs = real_requests(shape.requests, prefix_len=shape.prefix_len,
                         new_len=shape.new_len, decode_len=shape.decode_len,
                         id_prefix=id_prefix)
    t0 = time.perf_counter()
    try:
        out, rep, eng = serve_real(
            model, params, reqs, stages=shape.stages,
            chunk_size=shape.chunk_size, max_batch=shape.max_batch,
            io_channels=io_channels, kv_quant=kv_quant, kv_tier=kv_tier,
            verify=True)
    except AssertionError as e:
        # RestorationExecutor.verify raises this when a restored cache
        # differs from its full-prefill reference
        raise SmokeFailure(f"phase {name}: serving check failed: {e}") from e
    secs = time.perf_counter() - t0
    ex = eng.executor
    dp = out["datapath"]
    tokens, caches = {}, {}
    for r in reqs:
        rid = r.request_id
        if rid not in rep.restore_secs:
            raise SmokeFailure(f"phase {name}: {rid} never finished "
                               f"restoration, so it was never verified")
        o = ex.outputs(rid)
        logits = np.asarray(o["first_logits"], np.float32)
        if logits.shape != (1, model.cfg.vocab_size) \
                or not np.isfinite(logits).all():
            raise SmokeFailure(f"phase {name}: {rid} first-token logits "
                               f"have shape {logits.shape} or are not "
                               f"finite")
        if len(o["tokens"]) != shape.decode_len:
            raise SmokeFailure(f"phase {name}: {rid} produced "
                               f"{len(o['tokens'])} tokens, expected "
                               f"{shape.decode_len}")
        tokens[r.request_id[len(id_prefix):]] = o["tokens"]
        if keep_caches:
            caches[rid[len(id_prefix):]] = {
                f: np.asarray(a) for f, a in ex.live_cache(rid).items()}
    quant_calls = out["storage"]["kv_quant_calls"]
    record = {
        "phase": name,
        "model": out["model"],
        "kv_quant": kv_quant, "kv_tier": kv_tier,
        "io_channels": io_channels,
        "seconds_including_compile": secs,
        "restored_caches_verified": len(rep.restore_secs),
        "greedy_tokens": tokens,
        "ttft_engine_clock_s (bring-up output, not a measurement)":
            {rid: rep.ttfts[rid] for rid in sorted(rep.ttfts)},
        "kv_restore": {"pallas_launches": dp["pallas_launches"],
                       "oracle_runs": dp["oracle_runs"],
                       "resident_copies (device-local, not a fallback)":
                           dp["resident_copies"],
                       "device_moves": dp["device_moves"]},
        "kv_quant_calls": quant_calls,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
    }
    print(json.dumps(record), flush=True)
    if require_pallas:
        if dp["oracle_runs"]:
            raise SmokeFailure(
                f"phase {name}: {dp['oracle_runs']} staged restore runs "
                f"took the jnp oracle on the chip instead of the Pallas "
                f"kv_restore kernel")
        if not dp["pallas_launches"]:
            raise SmokeFailure(f"phase {name}: no Pallas kv_restore launch "
                               f"happened")
        if kv_quant == "int8" and not quant_calls.get("pallas"):
            raise SmokeFailure(f"phase {name}: int8 KV never ran the "
                               f"Pallas kv_quant kernel")
    del eng, rep, out
    gc.collect()
    return record, tokens, caches


def one_chip(model, params, shape: Shape, *, require_pallas: bool = True):
    """Phases A, B and warm A; A and warm A must decode the same tokens."""
    _, tok_a, _ = run_phase("A", model, params, shape, kv_quant="none",
                            kv_tier="host", id_prefix="a",
                            require_pallas=require_pallas)
    run_phase("B", model, params, shape, kv_quant="int8", kv_tier="remote",
              id_prefix="b", require_pallas=require_pallas)
    _, tok_w, _ = run_phase("A-warm", model, params, shape, kv_quant="none",
                            kv_tier="host", id_prefix="w",
                            require_pallas=require_pallas)
    if tok_w != tok_a:
        raise SmokeFailure(f"warm phase A decoded {tok_w}, cold phase A "
                           f"decoded {tok_a}")


def four_chips(model, params, shape: Shape, *, require_pallas: bool = True):
    """Phase A with one restoration channel per chip, then one channel:
    identical restored caches and greedy tokens."""
    import numpy as np
    rec4, tok4, c4 = run_phase("A-io4", model, params, shape,
                               kv_quant="none", kv_tier="host",
                               io_channels=4, id_prefix="c",
                               require_pallas=require_pallas,
                               keep_caches=True)
    if not rec4["kv_restore"]["device_moves"]:
        raise SmokeFailure("io-channels 4: no staged run crossed chips; "
                           "the per-chip channels were not exercised")
    _, tok1, c1 = run_phase("A-io1", model, params, shape, kv_quant="none",
                            kv_tier="host", io_channels=1, id_prefix="d",
                            require_pallas=require_pallas, keep_caches=True)
    if tok4 != tok1:
        raise SmokeFailure(f"io-channels 4 decoded {tok4}, io-channels 1 "
                           f"decoded {tok1}")
    for i in c1:
        for f in c1[i]:
            if not np.array_equal(c4[i][f], c1[i][f]):
                raise SmokeFailure(f"request {i}: cache field {f} differs "
                                   f"between io-channels 4 and 1")
    print(json.dumps({"phase": "compare io-channels 4 vs 1",
                      "identical_caches": sorted(c1),
                      "identical_tokens": True}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase A with --io-channels 4 and 1 on a "
                         "four-chip host and compare them")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
        from repro.launch.serve import build_real_model
    except ImportError as e:
        raise SmokeFailure(f"the repository's sources are not next to "
                           f"chip_smoke.py ({ROOT}/src): {e}") from e
    use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found platform '{dev.platform}' "
                           f"({dev.device_kind})")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SmokeFailure(f"--four-chips needs 4 chips, JAX found "
                           f"{len(devices)}")
    shape = Shape()
    t0 = time.perf_counter()
    model, params = build_real_model(shape.arch, layers=shape.layers,
                                     dtype=shape.dtype, seed=shape.seed)
    jax.block_until_ready(params)
    print(json.dumps({"phase": "init", "model": model.cfg.name,
                      "layers": model.cfg.num_layers, "dtype": shape.dtype,
                      "params": model.num_params(params),
                      "seconds": time.perf_counter() - t0,
                      "peak_bytes_in_use": _peak_bytes(dev)}), flush=True)
    if args.four_chips:
        four_chips(model, params, shape)
    else:
        one_chip(model, params, shape)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
