"""End-to-end serving driver.

Simulation at paper scale (default):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \
      --workload swe_bench --requests 64 --system cacheflow --bandwidth 10Gbps

Real execution on a reduced model (CPU): restoration is served from the
MATERIALIZED chunk-granular KV store (content-addressed dedup across
hbm/host/disk tiers; see DESIGN.md §10) — ``--kv-quant int8`` stores
sub-HBM tiers per-channel quantized, ``--store-dir`` materializes the disk
tier as .npz files, ``--evict`` drops (instead of parks) preempted caches
and restarts them from the store:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --real \
      --requests 4 --system cacheflow --kv-quant int8 --store-dir /tmp/kv

Real execution at published widths (one TPU v5e): ``--layers`` keeps every
width of the config and cuts only its depth; ``chip_smoke.py`` at the repo
root runs this path and checks it:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --real \
      --layers 18 --dtype bfloat16 --chunk-size 128 --prefix-len 2048 \
      --new-len 64 --requests 4 --max-batch 2

Schedule capture & replay (see repro/core/trace.py): ``--trace-out t.json``
records the restoration schedule of any run; ``--replay t.json`` re-executes
a captured schedule decision-for-decision with pinned durations —
analytically by default (bit-identical EngineResult), or on-device with
``--real`` (every dispatched op runs through a RestorationExecutor and each
restored cache is verified against full-prefill ground truth under the
captured interleaving).  On-device replay requires a trace whose geometry
fits the reduced model — capture it with ``--real --trace-out``; paper-scale
sim traces replay analytically.

Correctness tooling (see DESIGN.md §14): ``--sanitize`` (or
``CACHEFLOW_SANITIZE=1``) runs the engine under the runtime invariant
sanitizer and prints its counters in the report.  Captured traces lint
offline with
  PYTHONPATH=src python -m repro.analysis.lint_trace t.json
and the repo-specific static lint pass runs with
  PYTHONPATH=src python -m repro.analysis.codelint

Observability (see DESIGN.md §15): ``--telemetry`` (or
``CACHEFLOW_TELEMETRY=1``) collects the engine-wide metrics registry
(queue depth, batch sizes, gate outcomes, per-channel GB/s, tier
occupancy, per-request phase timestamps) into the report;
``--metrics-out m.json`` writes the snapshot to a file and
``--timeline-out t.json`` exports a Chrome trace-event timeline loadable
in https://ui.perfetto.dev.  Any captured trace renders offline with
  PYTHONPATH=src python -m repro.obs.timeline t.json
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core.baselines import BASELINES
from repro.core.trace import ScheduleTrace, TraceRecorder, replay_trace
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serving import (ChunkStore, RealServingEngine, Request,
                           SimServingEngine, TieredKVStore, generate)
from repro.serving.metrics import dumps_report
from repro.serving.workloads import WORKLOADS


def _save_trace(rec: TraceRecorder, path: str, arch: str = None):
    if arch is not None:
        rec.trace.meta["arch"] = arch   # replay sanity check (--real)
    rec.trace.save(path)
    # stderr: stdout carries the JSON report (`serve ... > report.json`)
    print(f"# schedule trace ({len(rec.trace.events)} events) -> {path}",
          file=sys.stderr)


def _save_timeline(trace: ScheduleTrace, path: str, telemetry=None):
    """Export the run's Perfetto timeline from its captured trace."""
    from repro.obs.timeline import trace_to_chrome
    doc = trace_to_chrome(trace, telemetry=telemetry)
    with open(path, "w") as f:
        f.write(dumps_report(doc))
    print(f"# perfetto timeline ({len(doc['traceEvents'])} events) -> "
          f"{path} (open in https://ui.perfetto.dev)", file=sys.stderr)


def _save_metrics(telemetry: dict, path: str):
    with open(path, "w") as f:
        f.write(dumps_report(telemetry))
    print(f"# telemetry snapshot -> {path}", file=sys.stderr)


def build_real_model(arch: str, *, layers: Optional[int] = None,
                     dtype: str = "float32", seed: int = 0):
    """The model of the real path, with random weights drawn from ``seed``.

    Without ``layers`` it is the config's ``.reduced()`` CPU-test model.
    With ``layers`` every published width of the config is kept and only
    its depth is cut to the first ``layers`` layers.  ``dtype`` is both the
    parameter and the compute dtype."""
    base = get_config(arch)
    if layers is None:
        cfg = base.reduced()
    else:
        if not 1 <= layers <= base.num_layers:
            raise SystemExit(f"--layers {layers}: '{arch}' has "
                             f"{base.num_layers} layers")
        cfg = dataclasses.replace(base, name=f"{base.name}-{layers}L",
                                  num_layers=layers)
    dt = jnp.dtype(dtype)
    model = build_model(cfg, param_dtype=dt, compute_dtype=dt)
    return model, model.init(jax.random.PRNGKey(seed))


def real_requests(n: int, *, prefix_len: Optional[int] = None,
                  new_len: int = 16, decode_len: int = 8,
                  preempt: str = "none", id_prefix: str = "r"
                  ) -> List[Request]:
    """The real path's request stream: prefixes of ``prefix_len`` tokens
    (64+32·i when None).  With a preemption policy armed, arrivals are
    staggered and every other request is urgent so admission pressure
    exercises it; without one, every request arrives at t=0."""
    reqs = []
    for i in range(n):
        plen = prefix_len if prefix_len is not None else 64 + 32 * i
        if preempt != "none":
            reqs.append(Request(f"{id_prefix}{i}", 0.1 * i, prefix_len=plen,
                                new_len=new_len, decode_len=decode_len,
                                priority=i % 2,
                                deadline=0.1 * i + (2.0 if i % 2 else 120.0)))
        else:
            reqs.append(Request(f"{id_prefix}{i}", 0.0, prefix_len=plen,
                                new_len=new_len, decode_len=decode_len))
    return reqs


def serve_real(model, params, requests: List[Request], *,
               system: str = "cacheflow", stages: int = 2,
               chunk_size: int = 16, max_batch: int = 8,
               io_channels: int = 1, kv_quant: str = "none",
               kv_tier: str = "host", store_dir: Optional[str] = None,
               datapath: str = "fused", preempt: str = "none",
               evict: bool = False, admission: str = "continuous",
               prefetch: bool = False, sanitize: Optional[bool] = None,
               telemetry: Optional[bool] = None, verify: bool = True,
               trace=None):
    """Serve ``requests`` through RealServingEngine → EngineCore →
    RestorationExecutor → ChunkStore/RestoreDatapath, verifying every
    restored cache against its full-prefill reference (``verify``).

    Restoration reads the MATERIALIZED chunk store: prefix KV lives as
    content-addressed, deduplicated chunks across hbm/host/disk tiers and
    load ops move their actual bytes.  Returns ``(report, ServingReport,
    engine)``; ``report`` is the JSON block ``serve --real`` prints."""
    cfg = model.cfg
    store = None
    if not cfg.attn_window:
        store = ChunkStore(chunk_size=chunk_size, quant=kv_quant,
                           store_dir=store_dir, default_tier=kv_tier)
    eng = RealServingEngine(model, params, system=system,
                            stages=min(stages, 2), chunk_size=chunk_size,
                            max_batch=max_batch, io_channels=io_channels,
                            preempt=preempt, evict=evict,
                            admission=admission, prefetch=prefetch,
                            kvstore=store, datapath=datapath,
                            sanitize=sanitize, telemetry=telemetry)
    rep = eng.serve(requests, verify=verify, trace=trace)
    out = {"system": system, "mode": "real",
           "model": {"name": cfg.name, "layers": cfg.num_layers,
                     "d_model": cfg.d_model,
                     "dtype": jnp.dtype(model.param_dtype).name},
           "chunk_size": chunk_size,
           "admission": admission,
           "lifecycle": rep.stats,
           "preemptions": sum(rep.preemptions.values()),
           "compute_busy": round(rep.compute_busy, 3),
           "io_busy": round(rep.io_busy, 3),
           "decode_busy": round(rep.decode_busy, 3),
           "overlap_decode_restore": round(rep.overlap_decode_restore, 3)}
    if rep.sanitizer is not None:
        out["sanitizer"] = rep.sanitizer
    if store is not None:
        out["storage"] = {
            "chunks": len(store.chunks), "dedup_hits": store.dedup_hits,
            "bytes_put": store.bytes_put,
            "bytes_transferred": store.bytes_transferred,
            "io_hits": store.io_hits,
            "skipped_transfers": store.skipped_transfers,
            "store_misses": store.store_misses,
            "forks": store.forks,
            "pool_blocks": store.pool.live_blocks(),
            "cow_copies": store.pool.cow_copies,
            "cow_bytes": store.pool.bytes_copied,
            "kv_quant_calls": dict(store.quant_calls)}
    if eng.datapath is not None:
        dp, ex = eng.datapath, eng.executor
        out["datapath"] = {
            "mode": datapath,
            "channels": len(dp.streams),
            "pallas_launches": dp.pallas_launches,
            "oracle_runs": dp.oracle_runs,
            "resident_copies": dp.resident_copies,
            "note": "resident_copies are device-local copies of "
                    "HBM-resident runs, not oracle fallbacks",
            "device_moves": dp.device_moves,
            "device_move_bytes": dp.device_move_bytes,
            "staged_puts": sum(st.puts for st in dp.streams),
            "staged_bytes": sum(st.bytes_staged for st in dp.streams),
            "fused_loads": ex.fused_loads,
            "legacy_loads": ex.legacy_loads,
            "load_dispatches": ex.load_dispatches,
            # measured host→device bytes/sec per engine channel (None
            # until a channel carries a measured transfer)
            "channel_gbps": [round(b / 1e9, 6) if b else None
                             for b in dp.bandwidths()]}
    elif store is not None:
        out["datapath"] = {"mode": "legacy",
                           "load_dispatches": eng.executor.load_dispatches}
    return out, rep, eng


def _replay(args) -> None:
    trace = ScheduleTrace.load(args.replay)
    if not trace.requests:
        raise SystemExit(f"--replay: trace {args.replay} contains no requests")
    recorder = TraceRecorder() if args.trace_out else None
    if args.real:
        # Rebuild a reduced model, re-prefill every captured request so the
        # executor holds its ground truth, then execute the captured
        # schedule op-for-op with verification.
        from repro.core.executor import RestorationExecutor
        t_arch = trace.meta.get("arch")
        if t_arch is not None and t_arch != args.arch:
            raise SystemExit(
                f"--replay --real: trace was captured on arch '{t_arch}' "
                f"but --arch is '{args.arch}'; pass --arch {t_arch}")
        cfg = get_config(args.arch).reduced()
        # On-device replay needs a trace captured on this reduced-model
        # geometry (e.g. from `--real --trace-out`): a paper-scale sim
        # trace references layers this model does not have and prefixes a
        # CPU prefill cannot reproduce in reasonable time.
        max_layer = max(p["layer_hi"] for r in trace.requests
                        for p in r["plans"])
        max_tokens = max(r["n_tokens"] for r in trace.requests)
        if max_layer > cfg.num_layers or max_tokens > 4096:
            raise SystemExit(
                f"--replay --real: trace geometry (layers<= {max_layer}, "
                f"prefix<= {max_tokens} tokens) does not fit the reduced "
                f"'{args.arch}' model ({cfg.num_layers} layers); capture the "
                f"trace with `--real --trace-out` instead")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        chunks = {p["chunk_size"] for r in trace.requests for p in r["plans"]}
        if len(chunks) > 1:
            raise SystemExit(
                f"--replay --real: heterogeneous chunk sizes {sorted(chunks)} "
                f"in trace; one executor serves one chunk granularity")
        ex = RestorationExecutor(model, params, chunk_size=chunks.pop(),
                                 stages=trace.meta["stages"])
        rng = jax.random.PRNGKey(args.seed)
        for r in trace.requests:
            rng, key = jax.random.split(rng)   # distinct ground truth per rid
            n = r["n_tokens"]
            if cfg.input_mode == "tokens":
                inputs = jax.random.randint(key, (1, n), 0, cfg.vocab_size)
            else:
                inputs = jax.random.normal(key, (1, n, cfg.d_model))
            ex.remember(r["request_id"], inputs)
            # lifecycle traces (schema v2) also re-execute the captured
            # suffix prefill + decode steps on device
            new_len = r.get("new_len", 0)
            decode_len = r.get("decode_len", 0)
            if new_len > 0 or decode_len > 0:
                rng, key = jax.random.split(rng)
                if cfg.input_mode == "tokens":
                    suffix = jax.random.randint(key, (1, new_len), 0,
                                                cfg.vocab_size) if new_len else None
                else:
                    suffix = jax.random.normal(key, (1, new_len, cfg.d_model)) \
                        if new_len else None
                ex.set_suffix(r["request_id"], suffix, decode_len=decode_len)
        res = replay_trace(trace, ex, verify=True, trace_out=recorder)
        mode = "replay-real"
    else:
        res = replay_trace(trace, trace_out=recorder)
        captured = trace.captured_result()
        if captured is not None and res != captured:
            raise SystemExit(
                "--replay: analytic replay diverged from the captured "
                "EngineResult (trace edited or engine behavior changed)")
        mode = "replay-sim"
    if recorder is not None:
        # propagate the source capture's arch tag so a re-captured trace
        # keeps the --real arch sanity check armed
        _save_trace(recorder, args.trace_out, arch=trace.meta.get("arch"))
    if args.timeline_out:
        _save_timeline(recorder.trace if recorder is not None else trace,
                       args.timeline_out)
    print(dumps_report({
        "mode": mode, "trace": args.replay,
        "requests": len(trace.requests),
        "dispatches": len(trace.dispatches()),
        "prefills": len(trace.prefills()),
        "decode_steps": len(trace.decode_steps()),
        "makespan": res.makespan,
        "compute_busy": round(res.compute_busy, 3),
        "io_busy": round(res.io_busy, 3),
        "decode_busy": round(res.decode_busy, 3)}, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--workload", default="swe_bench", choices=list(WORKLOADS))
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--system", default="cacheflow", choices=list(BASELINES))
    ap.add_argument("--bandwidth", default="10Gbps", choices=list(IO_BANDWIDTHS))
    ap.add_argument("--hardware", default="tpu_v5e", choices=list(HARDWARE))
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--max-batch", "--max-active", dest="max_batch",
                    type=int, default=8,
                    help="continuous-batching admission cap (engine-core "
                         "max_active); 0 = unlimited")
    ap.add_argument("--io-channels", type=int, default=1)
    ap.add_argument("--decode-len", type=int, default=-1,
                    help="output tokens per request (lifecycle decode); "
                         "-1 keeps the workload-drawn lengths (sim) or "
                         "uses 8 (real)")
    ap.add_argument("--preempt", default="none",
                    choices=["none", "priority", "deadline"],
                    help="admission-pressure policy: suspend the least-"
                         "beneficial in-flight restoration for a more "
                         "urgent arrival (resumes on a freed slot)")
    ap.add_argument("--admission", default="continuous",
                    choices=["continuous", "gang"],
                    help="'continuous' streams arrivals into freed decode "
                         "slots mid-flight (restoration overlaps the live "
                         "decode batch); 'gang' is the run-to-completion "
                         "baseline — the next batch is admitted only when "
                         "the whole current batch retires")
    ap.add_argument("--prefetch", action="store_true",
                    help="promote queued requests' KV up a storage tier on "
                         "idle channel time (the admission queue is a known "
                         "lookahead window), so admission-time restoration "
                         "starts from the faster tier")
    ap.add_argument("--burst-size", type=int, default=3,
                    help="bursty_priority workload: urgent requests per burst")
    ap.add_argument("--burst-every", type=float, default=4.0,
                    help="bursty_priority workload: seconds between bursts")
    ap.add_argument("--kv-tier", default="host",
                    choices=["hbm", "host", "remote"],
                    help="tier returning prefixes start in: 'hbm' is "
                         "device-resident (restoration transfers are "
                         "skipped entirely as dedup/residency hits), "
                         "'host' models warm DRAM reuse, and 'remote' the "
                         "cold disaggregated store (the real-mode chunk "
                         "store maps it to its disk tier), where "
                         "restoration dominates and admission pressure "
                         "(and preemption) is real")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="per-channel int8 compression of sub-HBM tiers "
                         "(kernels/kv_quant): real mode stores quantized "
                         "chunk bytes and dequantizes on promotion; sim "
                         "mode halves stored bytes / doubles effective "
                         "transfer bandwidth")
    ap.add_argument("--store-dir", metavar="DIR",
                    help="real mode: materialize the chunk store's bottom "
                         "tier as .npz files under DIR (in-memory blobs "
                         "when omitted)")
    ap.add_argument("--datapath", default="fused",
                    choices=["fused", "legacy"],
                    help="real mode restoration data path: 'fused' moves "
                         "each load op's chunks as ONE packed (int8-"
                         "quantized when --kv-quant int8) staging buffer "
                         "through a per-channel double-buffered transfer "
                         "stream and scatters with a single fused dequant "
                         "kernel launch (core/datapath.py + "
                         "kernels/kv_restore); 'legacy' keeps the "
                         "per-chunk/per-layer/per-field .at[].set() "
                         "baseline")
    ap.add_argument("--evict", action="store_true",
                    help="eviction-mode preemption: drop the victim's "
                         "partially-restored cache (instead of parking "
                         "it) and restart restoration from the KV store "
                         "on re-admission — for when host memory is tight")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sanitize", action="store_true",
                    help="run the engine under the runtime sanitizer "
                         "(repro.analysis.sanitizer): every scheduling "
                         "event is checked against the engine's "
                         "concurrency invariants and the report prints "
                         "the sanitizer counters; equivalent to "
                         "CACHEFLOW_SANITIZE=1")
    ap.add_argument("--telemetry", action="store_true",
                    help="collect the engine-wide metrics registry "
                         "(repro.obs): queue depth, admitted/decode batch "
                         "sizes, benefit-gate outcomes, preempt/abort "
                         "counts, per-channel busy and measured GB/s, "
                         "storage-tier occupancy and per-request phase "
                         "timestamps; the report carries the snapshot "
                         "under 'telemetry'; equivalent to "
                         "CACHEFLOW_TELEMETRY=1")
    ap.add_argument("--metrics-out", metavar="PATH",
                    help="write the full telemetry snapshot (metrics + "
                         "gauge time series + per-request phase "
                         "transitions) to PATH as strict JSON; implies "
                         "--telemetry")
    ap.add_argument("--timeline-out", metavar="PATH",
                    help="export the run's schedule as Chrome trace-event "
                         "JSON loadable in https://ui.perfetto.dev — one "
                         "track per engine resource, per-request lifecycle "
                         "flow arrows, aborted-op markers and counter "
                         "tracks (queue depth, tier bytes, per-channel "
                         "bandwidth); works with --replay too")
    ap.add_argument("--real", action="store_true",
                    help="run the model for real (the config's reduced "
                         "CPU-test widths unless --layers is given)")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="real mode: keep every published width of --arch "
                         "and cut only its depth to N layers.  For qwen3-8b "
                         "on one 16 GB TPU v5e use 18 of 36 layers (one of "
                         "two pipeline stages) with --dtype bfloat16: about "
                         "9.44 GB of weights, leaving about 6.5 GB for "
                         "caches; KV is 4 KiB per token per layer "
                         "(kv_bytes_per_token), 72 KiB per token at 18 "
                         "layers")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="real mode: parameter and compute dtype")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="real mode: restoration chunk (and store block) "
                         "size in tokens; use >= 128 on a TPU, where every "
                         "chunk x layer is one dispatch")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="real mode: prefix tokens of every request "
                         "(default 64+32*i for request i)")
    ap.add_argument("--new-len", type=int, default=16,
                    help="real mode: new-turn suffix tokens per request")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="capture the restoration schedule to a JSON trace")
    ap.add_argument("--replay", metavar="PATH",
                    help="re-execute a captured trace (pinned durations) "
                         "instead of scheduling fresh; --real replays it "
                         "on-device with per-request cache verification")
    args = ap.parse_args()
    use_compile_cache()

    if args.admission == "gang" and args.preempt != "none":
        raise SystemExit("--admission gang is the run-to-completion "
                         "baseline: no mid-flight admission, so preemption "
                         "policies do not apply (drop --preempt)")

    if args.metrics_out:
        args.telemetry = True

    if args.replay:
        _replay(args)
        return

    # --timeline-out renders from a captured trace, so it implies capture
    # (recording is observation-only; the schedule is unchanged)
    recorder = TraceRecorder() if (args.trace_out or args.timeline_out) \
        else None

    if args.real:
        model, params = build_real_model(args.arch, layers=args.layers,
                                         dtype=args.dtype, seed=args.seed)
        reqs = real_requests(
            args.requests, prefix_len=args.prefix_len, new_len=args.new_len,
            decode_len=args.decode_len if args.decode_len >= 0 else 8,
            preempt=args.preempt)
        out, rep, _ = serve_real(
            model, params, reqs, system=args.system, stages=args.stages,
            chunk_size=args.chunk_size, max_batch=args.max_batch,
            io_channels=args.io_channels, kv_quant=args.kv_quant,
            kv_tier=args.kv_tier, store_dir=args.store_dir,
            datapath=args.datapath, preempt=args.preempt, evict=args.evict,
            admission=args.admission, prefetch=args.prefetch,
            sanitize=args.sanitize or None,
            telemetry=args.telemetry or None, trace=recorder)
        if args.trace_out:
            _save_trace(recorder, args.trace_out, arch=args.arch)
        _emit_outputs(out, rep, recorder, args)
        return

    cfg = get_config(args.arch)
    if args.workload == "bursty_priority":
        from repro.serving.workloads import bursty_priority
        reqs = bursty_priority(args.requests, seed=args.seed,
                               burst_size=args.burst_size,
                               burst_every=args.burst_every)
    else:
        reqs = generate(args.workload, args.requests, seed=args.seed)
    if args.decode_len >= 0:
        for r in reqs:
            r.decode_len = args.decode_len
    store = TieredKVStore(remote_bw=IO_BANDWIDTHS[args.bandwidth],
                          quant=args.kv_quant)
    eng = SimServingEngine(cfg, HARDWARE[args.hardware],
                           io_bandwidth=IO_BANDWIDTHS[args.bandwidth],
                           system=args.system, stages=args.stages,
                           max_batch=args.max_batch, kvstore=store,
                           io_channels=args.io_channels,
                           preempt=args.preempt, evict=args.evict,
                           kv_tier=args.kv_tier, admission=args.admission,
                           prefetch=args.prefetch,
                           sanitize=args.sanitize or None,
                           telemetry=args.telemetry or None)
    rep = eng.run(reqs, trace=recorder)
    if args.trace_out:
        _save_trace(recorder, args.trace_out, arch=args.arch)
    out = {
        "system": args.system, "workload": args.workload,
        "bandwidth": args.bandwidth, "hardware": args.hardware,
        "stages": args.stages, "preempt": args.preempt,
        "admission": args.admission,
        "lifecycle": rep.stats,
        "preemptions": sum(rep.preemptions.values()),
        "compute_busy": round(rep.compute_busy, 3),
        "io_busy": round(rep.io_busy, 3),
        "decode_busy": round(rep.decode_busy, 3),
        "overlap_decode_restore": round(rep.overlap_decode_restore, 3)}
    if rep.sanitizer is not None:
        out["sanitizer"] = rep.sanitizer
    _emit_outputs(out, rep, recorder, args)


def _emit_outputs(out: dict, rep, recorder, args):
    """Shared report/metrics/timeline emission for the sim and real paths.
    stdout gets the report (with the telemetry counters inlined when
    collected); the full snapshot and the Perfetto timeline go to their
    --*-out files."""
    if rep.telemetry is not None:
        # counters only on stdout — the gauge series and phase timelines
        # can be large; --metrics-out carries the full snapshot
        out["telemetry"] = {"counters": rep.telemetry["metrics"]["counters"]}
    if args.metrics_out:
        _save_metrics(rep.telemetry, args.metrics_out)
    if args.timeline_out:
        _save_timeline(recorder.trace, args.timeline_out,
                       telemetry=rep.telemetry)
    print(dumps_report(out))


if __name__ == "__main__":
    main()
