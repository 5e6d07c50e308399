#!/usr/bin/env python3
"""The chip benchmark of the served restoration path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``<cell>`` is a ``workloads`` entry of
``BENCHMARK.json``; its configuration file, traffic mix, per-layer
readers and correctness limit are found by name under ``bench/``.

Set-up (counted in ``setup_s``): weights drawn from the seed on the chip,
the engine built, the pool of sessions stored through the executor (their
previous turn), and every turn of the mix's catalog served once with the
engine's own plans, which compiles the programs of the catalog's lengths.
Window: waves of returning sessions, W at a time, until ``--seconds`` is
used up; no wave starts after the deadline.  The engine's scheduler picks
each turn's split between recompute and load from measured op times, and
the program compiles one program per split; those it meets first in the
window compile there, counted by ``window_compiles`` and paid in the
turns' times.  The window neither reads nor writes the persistent
compilation cache, so what it compiles does not depend on the runs that
came before it in the checkout.  Then the program's state is freed and a
sample of the served turns is compared with the float32 reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last the numbers compared with their limits.  The
same numbers end standard error.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness.arch import BenchError  # noqa: E402

TRACE_DIR = os.path.join(BENCH, ".trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry and every file it is found by, all under ``root``:
    its configuration, the configuration's family, traffic and limits."""
    from harness import arch as arch_mod
    from harness import traffic
    bench = os.path.join(root, os.path.relpath(BENCH, ROOT))
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    mix = traffic.load(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    with open(os.path.join(bench, "limits", name + ".json")) as f:
        limits = json.load(f)

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    a = arch_mod.load(os.path.join(root, cfg["file"]), cfg["name"],
                      os.path.join(bench, "families"))
    return {"cell": cell, "arch": a,
            "mix": mix, "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def import_program(root: str = ROOT):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program's sources are not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_compile_cache():
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` at the checkout's root (a fixed path, so
    later runs of the checkout hit).  Every program is kept, however fast
    it compiled, so a later run loads rather than compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def persistent_cache(on: bool):
    """Let compiles from here on read and write the persistent
    compilation cache, or neither."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", on)
    cc.reset_cache()


def chips(want: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} "
                         f"({devs[0].device_kind})")
    if len(devs) < want:
        raise BenchError(f"the cell needs {want} chips, JAX found {len(devs)}")
    return devs


def log(t_process: float, what: str, device=None):
    """One line of progress on standard error: seconds since the process
    started, and the chip's memory in use where JAX reports it."""
    mem = ""
    if device is not None:
        stats = device.memory_stats() or {}
        if "bytes_in_use" in stats:
            mem = (f" in_use={stats['bytes_in_use']}"
                   f" peak={stats.get('peak_bytes_in_use')}")
    print(f"[{time.perf_counter() - t_process:8.2f}s] {what}{mem}",
          file=sys.stderr, flush=True)


def e2e_metrics(specs, turns, window_s: float, setup_s: float) -> dict:
    from harness.stats import percentile
    ttft = [t.ttft_s for t in turns]
    gaps = [g for t in turns for g in t.gaps_s]
    values = {
        "ttft_p50_s": statistics.median(ttft),
        "ttft_p90_s": percentile(ttft, 90),
        "tbt_p90_s": percentile(gaps, 90) if gaps else None,
        "turns_per_s": len(turns) / window_s,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in specs if values.get(m["name"]) is not None}


def run_cell(c: dict, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, hook: Optional[Callable] = None,
             control: bool = False, t_process: float = T_PROCESS) -> dict:
    """One run of a cell; returns the result object (not printed)."""
    import jax
    from harness import check, peaks as peaks_mod, readers, serving, trace
    from harness import traffic, weights as weights_mod
    from harness.arch import family_of
    from harness.probe import CompileCounter, Probe

    a, mix, cell = c["arch"], c["mix"], c["cell"]
    devs = chips(int(cell["chips"]), require_tpu)
    dev = devs[0]
    pk = peaks_mod.peaks_for(dev.device_kind) if require_tpu else None
    counter = CompileCounter.get()

    w = weights_mod.make_weights(a, seed)
    jax.block_until_ready(w)
    log(t_process, f"weights of {a.name} drawn", dev)
    params = family_of(a).program_params(w)
    model = serving.build_model(a, params)
    engine = serving.build_engine(a, model, params, mix["store"])
    probe = Probe(engine, annotate=traced, require_pallas=require_tpu)
    probe.attach()
    pool = traffic.sessions(mix)
    serving.remember_pool(engine, pool, seed, a.vocab)
    log(t_process, f"pool of {len(pool)} sessions stored "
        f"({sum(s.prefix for s in pool)} prefix tokens)", dev)
    if serving.pool_dedup_hits(engine):
        raise BenchError(f"the pool's sessions share chunks "
                         f"({serving.pool_dedup_hits(engine)} dedup hits)")
    log(t_process, f"compiled so far: {counter.take_names()}")
    warm = traffic.warmup_waves(mix, seed, pool, a.max_batch, a.vocab)
    for wave in warm:
        serving.serve_wave(engine, probe, wave)
    log(t_process, f"warm-up: {sum(len(w) for w in warm)} catalog turns "
        f"served, compiled {counter.take_names()}", dev)
    if hook is not None:
        hook(engine)
    probe.reset()

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    persistent_cache(False)
    compiles0 = counter.total()
    t_start = time.perf_counter()
    deadline = t_start + seconds
    turns, index = [], 0
    with probe.span("window"):
        while time.perf_counter() < deadline:
            turns += serving.serve_wave(
                engine, probe,
                traffic.wave(mix, seed, index, pool, a.max_batch, a.vocab))
            index += 1
        t_end = time.perf_counter()
    compiles = counter.total() - compiles0
    persistent_cache(True)
    if traced:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    setup_s = t_start - t_process
    peak = serving.peak_bytes(dev)
    rec = probe.rec
    log(t_process, f"window: {index} waves, {len(turns)} turns in "
        f"{window_s:.2f}s, {compiles} compiles: "
        f"{counter.take_names()}", dev)
    serving.free_engine(engine, probe)
    del engine, probe, model, params
    gc.collect()

    verdict = check.judge(w, a, turns, seed, pool, c["limits"],
                          control=control)
    log(t_process, "reference comparison done", dev)
    result = {"correct": verdict["correct"], "attempted": len(turns),
              "failed": sum(1 for t in turns if t.failed)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if traced:
        tr = trace.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = readers.Context(a, mix["store"], turns, rec, window_s, compiles,
                              pk, tr)
        metrics = {}
        for m in c["per_layer"]:
            v = readers.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window()
        ops = tr.ops.get(0, [])
        device["busy_s"] = trace.busy_ns(ops, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace.top_ops(ops, lo, hi)],
            "idle_gaps": [list(x) for x in trace.idle_by_span(
                ops, tr.spans, lo, hi)]}
    else:
        result["metrics"] = e2e_metrics(c["end_to_end"], turns, window_s,
                                        setup_s)
    result["device"] = device
    result["checks"] = verdict["checks"]
    return result


def report(result: dict):
    """Print the compared numbers as the last lines of standard error and
    the result as the last line of standard output."""
    for name, v in result["checks"].items():
        if isinstance(v, dict):
            print(f"check {name} {v['value']} limit {v['limit']}",
                  file=sys.stderr)
        else:
            print(f"check {name} {v}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = load_cell(args.workload)
        import_program()
        use_compile_cache()
        result = run_cell(c, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
