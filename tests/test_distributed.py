"""Distribution layer tests. Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test process
keeps seeing 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import sharding as shr
from repro.models import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_param_pspecs_cover_every_leaf():
    for arch in ("qwen3-8b", "deepseek-v2-236b", "recurrentgemma-2b", "rwkv6-7b"):
        model = build_model(get_config(arch))
        specs = model.param_specs()
        pspecs = shr.param_pspecs(model, "train")
        n_leaves = len(jax.tree.leaves(specs))
        n_specs = len(jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        assert n_specs == n_leaves


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "deepseek-v2-236b",
                                  "recurrentgemma-2b", "rwkv6-7b"])
@pytest.mark.parametrize("mode", ["train", "serve_tp", "serve_2d"])
def test_pspec_divisibility_on_production_mesh(arch, mode):
    """Every sharded dim divides the 16×16 production mesh axes (jit would
    reject uneven input shardings)."""
    model = build_model(get_config(arch), param_dtype=jax.numpy.bfloat16)
    specs = model.param_specs()
    pspecs = shr.param_pspecs(model, mode)
    axis_size = {"pod": 2, "data": 16, "model": 16}

    def check(path, sds, spec):
        for d, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([axis_size[a] for a in axes]))
            assert sds.shape[d] % size == 0, \
                (jax.tree_util.keystr(path), sds.shape, tuple(spec))

    jax.tree_util.tree_map_with_path(
        check, specs, pspecs)


def test_sharded_train_and_decode_match_single_device():
    """On an 8-device mesh, one sharded train step and one sharded decode
    step produce the same numbers as the unsharded run."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.distributed import sharding as shr
        from repro.launch.mesh import make_mesh
        from repro.training import AdamWConfig, DataConfig, batch_at, \\
            init_opt_state, make_train_step

        cfg = get_config('qwen1.5-0.5b').reduced(num_heads=4, num_kv_heads=4,
                                                 d_model=128, d_ff=256)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
        batch = batch_at(dc, 0)
        step = make_train_step(m, AdamWConfig(total_steps=10))

        # single device reference
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

        mesh = make_mesh((4, 2), ('data', 'model'))
        pspecs = shr.to_named(mesh, shr.param_pspecs(m, 'train'))
        ospecs = shr.to_named(mesh, shr.opt_pspecs(m, 'train'))
        bspecs = shr.to_named(mesh, shr.data_pspecs(cfg, mesh, 'train', 8))
        with jax.set_mesh(mesh):
            p_sh, o_sh, m_sh = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                                       out_shardings=(pspecs, ospecs, None))(
                params, opt, batch)
        l_ref = np.asarray(jax.tree.leaves(p_ref)[0], np.float32)
        l_sh = np.asarray(jax.tree.leaves(p_sh)[0], np.float32)
        err = float(np.max(np.abs(l_ref - l_sh)))
        loss_diff = abs(float(m_ref['loss']) - float(m_sh['loss']))

        # decode parity
        last, cache = m.prefill(params, batch['tokens'][:, :16])
        tok = jnp.argmax(last, -1).astype(jnp.int32)
        log_ref, _ = m.decode_step(params, tok, cache, 16)
        cspec = shr.to_named(mesh, shr.cache_pspecs(m, mesh, 8, 16))
        with jax.set_mesh(mesh):
            dstep = jax.jit(m.decode_step,
                            in_shardings=(pspecs, shr.to_named(mesh,
                                shr.data_pspecs(cfg, mesh, 'decode', 8)), cspec, None),
                            out_shardings=(None, cspec))
            log_sh, _ = dstep(params, tok, cache, 16)
        derr = float(np.max(np.abs(np.asarray(log_ref, np.float32)
                                   - np.asarray(log_sh, np.float32))))
        print(json.dumps({'err': err, 'loss_diff': loss_diff, 'decode_err': derr}))
    """)
    out = _run_subprocess(code)
    assert out["err"] < 2e-4, out
    assert out["loss_diff"] < 1e-4, out
    assert out["decode_err"] < 2e-3, out


def test_elastic_reshard_roundtrip():
    """Checkpoint on a 4x2 mesh, resume on 2x4 — values identical."""
    code = textwrap.dedent("""
        import json, tempfile
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.distributed import sharding as shr
        from repro.distributed.elastic import replace_on_mesh, validate_divisibility
        from repro.launch.mesh import make_mesh
        from repro.training import CheckpointManager

        cfg = get_config('qwen1.5-0.5b').reduced(num_heads=4, num_kv_heads=4,
                                                 d_model=128, d_ff=256)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        pspec = shr.param_pspecs(m, 'train')
        mesh_a = make_mesh((4, 2), ('data', 'model'))
        mesh_b = make_mesh((2, 4), ('data', 'model'))
        placed = replace_on_mesh(params, pspec, mesh_a)
        with tempfile.TemporaryDirectory() as d:
            ck = CheckpointManager(d)
            ck.save(0, placed)
            _, restored = ck.restore(placed)
            assert validate_divisibility(restored, pspec, mesh_b) == []
            placed_b = replace_on_mesh(restored, pspec, mesh_b)
            a = np.asarray(jax.tree.leaves(params)[0], np.float32)
            b = np.asarray(jax.tree.leaves(placed_b)[0], np.float32)
            print(json.dumps({'equal': bool(np.array_equal(a, b))}))
    """)
    assert _run_subprocess(code)["equal"] is True


def test_compressed_psum_under_shard_map():
    """int8 error-feedback mean over a mesh axis ≈ exact mean."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.training.compression import error_feedback_psum

        mesh = make_mesh((8,), ('pod',))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096), jnp.float32)

        def f(xl):
            mean, res = error_feedback_psum(xl[0], 'pod')
            return mean[None], res[None]

        mean, res = jax.jit(shard_map(f, mesh=mesh, in_specs=P('pod', None),
                                      out_specs=P('pod', None)))(x)
        exact = x.mean(axis=0)
        rel = float(jnp.linalg.norm(mean[0] - exact) / jnp.linalg.norm(exact))
        print(json.dumps({'rel': rel}))
    """)
    assert _run_subprocess(code)["rel"] < 0.02


def test_io_channels_on_other_devices_match_one_channel():
    """Restoration channels pinned to devices other than the live cache's
    hand their staged bytes over by an explicit move: serving with one
    channel per device gives the same verified caches and greedy tokens
    as one channel (unmoved, the pool write refused the mixed devices)."""
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from repro.launch.serve import build_real_model, real_requests, serve_real

        model, params = build_real_model('qwen3-8b')
        runs = {}
        for ch in (4, 1):
            reqs = real_requests(4, prefix_len=128, new_len=8, decode_len=2,
                                 id_prefix=f'c{ch}-')
            out, rep, eng = serve_real(model, params, reqs, io_channels=ch,
                                       chunk_size=16)
            ex = eng.executor
            runs[ch] = dict(
                moves=out['datapath']['device_moves'],
                verified=len(rep.restore_secs),
                tokens=[ex.outputs(r.request_id)['tokens'] for r in reqs],
                caches=[{f: np.asarray(a) for f, a in
                         ex.live_cache(r.request_id).items()} for r in reqs])
        same = all(np.array_equal(a[f], b[f])
                   for a, b in zip(runs[4]['caches'], runs[1]['caches'])
                   for f in a)
        print(json.dumps({'moves4': runs[4]['moves'],
                          'moves1': runs[1]['moves'],
                          'verified': [runs[4]['verified'], runs[1]['verified']],
                          'same_tokens': runs[4]['tokens'] == runs[1]['tokens'],
                          'same_caches': same}))
    """)
    r = _run_subprocess(code)
    assert r["moves4"] > 0 and r["moves1"] == 0
    assert r["verified"] == [4, 4]
    assert r["same_tokens"] and r["same_caches"]
