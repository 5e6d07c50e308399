import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract the roofline terms.

For each cell this:
  1. builds the model + sharding rules for the mesh,
  2. jits the right step (train_step / prefill_step / decode_step) with
     explicit in/out shardings,
  3. ``.lower().compile()`` — success proves the distribution config is
     coherent (sharding divisibility, collectives, memory),
  4. records ``memory_analysis()`` (bytes/device), ``cost_analysis()``
     (FLOPs/bytes, per-device post-SPMD), and per-kind collective bytes
     parsed from the compiled HLO,
  5. writes one JSON per cell under benchmarks/results/dryrun/.

Run one cell:   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape decode_32k
Run the sweep:  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
(the sweep shells out one subprocess per cell so XLA state never accumulates)
"""
import argparse
import json
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.config import SHAPES, ModelConfig, ShapeConfig, supports_shape
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed import sharding as shr
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training import AdamWConfig, init_opt_state, make_train_step
from repro.training.optimizer import OptState

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# Serving MoE dispatch uses capacity-factor routing in the compiled plan
# (restoration-equality paths on real runs are dropless; see DESIGN.md).
_MOE_GROUPS = {"train": 16, "prefill": 16, "decode": 1}


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.input_mode == "tokens":
            return {"batch": {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}}
        return {"batch": {
            "embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}}
    if shape.kind == "prefill":
        if cfg.input_mode == "tokens":
            return {"inputs": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"inputs": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)}
    # decode: one new token against a seq_len cache
    if cfg.input_mode == "tokens":
        tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct((b, cfg.d_model), jnp.bfloat16)
    return {"tokens": tok, "pos": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# collective-byte extraction from compiled HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "u4": 1, "s4": 1}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+\[[^\]]*\])(?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved over links, by collective kind.

    Ring-algorithm accounting from the per-device (post-SPMD) module:
      all-gather R bytes result, group n: (n-1)/n · R
      reduce-scatter result R: (n-1) · R     (operand is n·R per device)
      all-reduce result R: 2(n-1)/n · R
      all-to-all result R: (n-1)/n · R
      collective-permute result R: R
    """
    out = {k: 0.0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                            "all-to-all", "collective-permute")}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str = m.group(1) or m.group(2)
        kind = m.group(3)
        r = _shape_bytes(shape_str)
        g = _GROUP_RE.search(line)
        n = int(g.group(2)) if g else 2
        if kind == "all-gather":
            moved = r * (n - 1) / max(1, n)
        elif kind == "reduce-scatter":
            moved = r * (n - 1)
        elif kind == "all-reduce":
            moved = 2 * r * (n - 1) / max(1, n)
        elif kind == "all-to-all":
            moved = r * (n - 1) / max(1, n)
        else:
            moved = r
        out[kind] += moved
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# per-cell dry run
# ---------------------------------------------------------------------------


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                mode_override=None, print_hlo: bool = False,
                decode_append: bool = False, restore_chunk: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape_name, "skipped": "full-attn @500k"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mode = mode_override or shr.choose_mode(cfg, shape)
    is_train = shape.kind == "train"
    model = build_model(
        cfg,
        param_dtype=jnp.float32 if is_train else jnp.bfloat16,
        compute_dtype=jnp.bfloat16,
        backend="flash",
        remat_policy="nothing" if is_train else "none",
        moe_groups=_MOE_GROUPS[shape.kind],
        moe_dropless=False)
    pspecs = shr.to_named(mesh, shr.param_pspecs(model, mode))
    specs = input_specs(cfg, shape)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = AdamWConfig()
            # microbatch = one sequence per (pod×data) batch shard: bounds
            # activation liveness while keeping the batch axes fully sharded
            shards = 1
            for a in shr.batch_axes(mesh):
                shards *= mesh.shape[a]
            accum = max(1, shape.global_batch // shards)
            step = make_train_step(model, opt_cfg, grad_accum=accum)
            params_sds = model.param_specs()
            opt_sds = jax.eval_shape(init_opt_state, params_sds)
            ospecs = shr.to_named(mesh, shr.opt_pspecs(model, mode))
            bspecs = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, "train",
                                                        shape.global_batch))
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ospecs, bspecs),
                             out_shardings=(pspecs, ospecs, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_sds, opt_sds, specs["batch"])
        elif shape.kind == "prefill" and not restore_chunk:
            def prefill_step(params, inputs):
                return model.prefill(params, inputs)
            params_sds = model.param_specs()
            cache_specs = shr.to_named(mesh, shr.cache_pspecs(
                model, mesh, shape.global_batch, shape.seq_len))
            ispec = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, "prefill",
                                                       shape.global_batch))
            jitted = jax.jit(prefill_step,
                             in_shardings=(pspecs, ispec),
                             out_shardings=(None, cache_specs))
            lowered = jitted.lower(params_sds, specs["inputs"])
        elif shape.kind == "prefill" and restore_chunk:
            # THE paper step: recompute-pointer chunk prefill against a
            # restored prefix cache (token-wise restoration at scale).
            C = 2048
            params_sds = model.param_specs()
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_specs = shr.to_named(mesh, shr.cache_pspecs(
                model, mesh, shape.global_batch, shape.seq_len))
            if cfg.input_mode == "tokens":
                chunk_sds = jax.ShapeDtypeStruct((shape.global_batch, C), jnp.int32)
            else:
                chunk_sds = jax.ShapeDtypeStruct(
                    (shape.global_batch, C, cfg.d_model), jnp.bfloat16)
            ispec = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, "prefill",
                                                       shape.global_batch))

            def restore_chunk_step(params, chunk, cache, start_pos):
                return model.prefill_chunk(params, chunk, cache, start_pos)
            jitted = jax.jit(restore_chunk_step,
                             in_shardings=(pspecs, ispec, cache_specs, None),
                             out_shardings=(None, cache_specs),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_sds, chunk_sds, cache_sds,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "decode" and decode_append and cfg.is_uniform:
            # §Perf optimisation: read-only cache + small append tail
            W = 64
            params_sds = model.param_specs()
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            tail_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, W))
            cache_specs = shr.to_named(mesh, shr.cache_pspecs(
                model, mesh, shape.global_batch, shape.seq_len))
            tail_specs = shr.to_named(mesh, shr.cache_pspecs(
                model, mesh, shape.global_batch, W))
            tspec = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, "decode",
                                                       shape.global_batch))

            def decode_append_step(params, tokens, cache, tail, tail_len, pos):
                return model.decode_step_append(params, tokens, cache, tail,
                                                tail_len, pos)
            jitted = jax.jit(decode_append_step,
                             in_shardings=(pspecs, tspec, cache_specs,
                                           tail_specs, None, None),
                             out_shardings=(None, tail_specs),
                             donate_argnums=(3,))
            lowered = jitted.lower(params_sds, specs["tokens"], cache_sds,
                                   tail_sds, specs["pos"], specs["pos"])
        else:
            def decode_step(params, tokens, cache, pos):
                return model.decode_step(params, tokens, cache, pos)
            params_sds = model.param_specs()
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_specs = shr.to_named(mesh, shr.cache_pspecs(
                model, mesh, shape.global_batch, shape.seq_len))
            tspec = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, "decode",
                                                       shape.global_batch))
            jitted = jax.jit(decode_step,
                             in_shardings=(pspecs, tspec, cache_specs, None),
                             out_shardings=(None, cache_specs),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_sds, specs["tokens"], cache_sds,
                                   specs["pos"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    # trip-count-corrected accounting (cost_analysis counts while bodies once)
    from repro.launch.hlo_cost import analyze as hlo_analyze
    corrected = hlo_analyze(hlo)
    pc = cfg.param_counts()
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode, "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": float(ca.get("flops", -1.0)),
        "bytes_per_device": float(ca.get("bytes accessed", -1.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
                + ma.output_size_in_bytes + ma.temp_size_in_bytes,
        },
        "collectives": colls,
        "corrected": {
            "dot_flops_per_device": corrected["dot_flops"],
            "collective_bytes": corrected["collective_bytes"],
            "collective_total_bytes": corrected["collective_total_bytes"],
            "while_trip_counts": corrected["while_trip_counts"],
        },
        "params_total": pc["total"], "params_active": pc["active"],
        "params_embedding": pc["embedding"],
    }
    if print_hlo:
        result["hlo"] = hlo
    return result


def cells(multi_pod_mode: str):
    pods = {"single": [False], "multi": [True], "both": [False, True]}[multi_pod_mode]
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            for mp in pods:
                yield arch, shape_name, mp, supports_shape(cfg, shape)


def _result_path(arch: str, shape_name: str, multi_pod: bool) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh = "2x16x16" if multi_pod else "16x16"
    return os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--mode", default=None, help="override sharding mode")
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape_name, mp, ok in cells(args.multi_pod):
            path = _result_path(arch, shape_name, mp)
            if os.path.exists(path) and not args.force:
                print(f"[cached] {path}")
                continue
            if not ok:
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name,
                               "mesh": "2x16x16" if mp else "16x16",
                               "skipped": "full-attn @500k"}, f, indent=1)
                print(f"[skip]   {arch} × {shape_name} (full-attn @500k)")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--multi-pod", "multi" if mp else "single"]
            print(f"[run]    {arch} × {shape_name} × {'2x16x16' if mp else '16x16'}",
                  flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ, "PYTHONPATH": "src"})
            if r.returncode != 0:
                failures.append((arch, shape_name, mp, r.stderr[-2000:]))
                print(r.stderr[-2000:])
        if failures:
            print(f"\n{len(failures)} FAILURES:")
            for a, s, mp, err in failures:
                print(f"  {a} × {s} × {'multi' if mp else 'single'}")
            sys.exit(1)
        print("\nall cells compiled OK")
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    mp = args.multi_pod == "multi"
    res = dryrun_cell(args.arch, args.shape, mp, mode_override=args.mode)
    path = _result_path(args.arch, args.shape, mp)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps({k: v for k, v in res.items() if k != "hlo"}, indent=1))
    print(f"\nmemory_analysis: {res.get('memory')}")
    print(f"cost_analysis flops/device: {res.get('flops_per_device'):.3e}")


if __name__ == "__main__":
    main()
