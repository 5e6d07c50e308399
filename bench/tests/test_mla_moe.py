"""The latent-attention, expert-share family (``bench/families/mla_moe.py``).

* A tiny configuration of the family enters by files alone: a root that
  holds a copy of ``BENCHMARK.json`` with one more cell, and the cell's
  configuration, family, traffic and limits, runs through ``run.load_cell``
  and ``run.run_cell`` to a correct result, reaches every entry point of
  the family, and writes nothing in the checkout; the fp8 control of the
  same cell, and each fault of ``harness.faults`` planted in its window,
  read not correct under the real cell's limits.
* The benchmark configuration's expert share is the program's: the held
  count is the file's ``n_routed_experts``, the only key in ``reduced``,
  and the served engine reports it as the gauge ``moe.experts_held``.
* The family's work counts at the published widths.
"""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

import run
from harness import arch, peaks, readers
from harness.faults import FAULTS
from test_families import _checkout_files

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "deepseek-v2-lite-ep8.long-history-chat"
ENTRY_POINTS = ("parse", "draw", "program_params", "program_config",
                "layers", "token_flops", "head_flops",
                "kv_bytes_per_token_layer", "kv_scale_bytes_per_chunk")

COUNTED_FAMILY = '''"""The mla_moe family under another name, counting the
calls of each entry point."""
from collections import Counter

from harness.arch import family

CALLS = Counter()
_family = family("mla_moe")


def _counted(name):
    def call(*args, **kw):
        CALLS[name] += 1
        return getattr(_family, name)(*args, **kw)
    return call


for _name in %r:
    globals()[_name] = _counted(_name)
''' % (ENTRY_POINTS,)


def _root(tmp_path) -> str:
    """A root with a copy of BENCHMARK.json and one more cell,
    ``tiny-mla-moe.chat``, whose configuration names a counted copy of
    the family; its limits are the real cell's."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    for d in ("configs", "families", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-mla-moe",
        "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
        "file": "bench/configs/tiny-mla-moe.json", "reduced": [],
        "why": "the latent-attention expert-share family at CPU widths"})
    spec["workloads"].append({
        "name": "tiny-mla-moe.chat", "config": "tiny-mla-moe",
        "traffic": "tiny-mla-moe", "chips": 1,
        "why": "a cell of the family added by files alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with open(os.path.join(HERE, "tiny-mla-moe.json")) as f:
        cfg = dict(json.load(f), family="counted_mla_moe")
    (bench / "configs" / "tiny-mla-moe.json").write_text(json.dumps(cfg))
    (bench / "families" / "counted_mla_moe.py").write_text(COUNTED_FAMILY)
    shutil.copy(os.path.join(HERE, "tiny-host.json"),
                bench / "traffic" / "tiny-mla-moe.json")
    with open(os.path.join(run.BENCH, "limits", CELL + ".json")) as f:
        limits = dict(json.load(f), served_tokens=40)
    (bench / "limits" / "tiny-mla-moe.chat.json").write_text(
        json.dumps(limits))
    return str(root)


def test_the_family_enters_by_files_alone_and_is_correct(tmp_path):
    root = _root(tmp_path)
    before = _checkout_files()
    c = run.load_cell("tiny-mla-moe.chat", root)
    fam = arch.family_of(c["arch"])
    assert fam.__file__ == os.path.join(root, "bench", "families",
                                        "counted_mla_moe.py")
    r = run.run_cell(c, 2**31 + 29, 1.0, False, require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the fp8 control, in the program's place, fails the same limits
    ctl = run.run_cell(c, 2**31 + 29, 1.0, False, require_tpu=False,
                       control=True)
    assert not ctl["correct"], ctl["checks"]
    # what a traced run on the chip reads through the family
    turns = [SimpleNamespace(prefix=64, suffix=16, outputs=12)]
    ops = [SimpleNamespace(kind="load", from_reference=False,
                           tokens=(0, 48), layers=(0, 2), t0=1.0, t1=1.5)]
    for quant in ("none", "int8"):
        ctx = readers.Context(c["arch"], {"quant": quant}, turns,
                              SimpleNamespace(ops=ops), 1.0, 0,
                              peaks.peaks_for("TPU v5 lite"))
        assert readers.reader("mfu.decode")(ctx) > 0
        assert readers.reader("load_gbps")(ctx) > 0
    assert sorted(fam.CALLS) == sorted(ENTRY_POINTS)
    assert _checkout_files() == before


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_makes_it_incorrect(tmp_path, fault):
    import jax.numpy as jnp
    from repro.models.model import build_model
    c = run.load_cell("tiny-mla-moe.chat", _root(tmp_path))
    a = c["arch"]
    # the harness's model is cached per configuration; a fault that
    # replaces its decode step must not outlive this test
    model = build_model(arch.family_of(a).program_config(a),
                        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
    step = model.decode_step_jit
    try:
        r = run.run_cell(c, 2**31 + 29, 1.0, False, require_tpu=False,
                         hook=FAULTS[fault])
    finally:
        model.decode_step_jit = step
    assert not r["correct"], r["checks"]


def _lite():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = {c["name"]: c for c in spec["configs"]}["deepseek-v2-lite-ep8"]
    path = os.path.join(run.ROOT, entry["file"])
    with open(path) as f:
        return entry, json.load(f), arch.load(path)


def test_the_expert_share_is_the_reduced_key():
    from repro.models.model import Model
    entry, d, a = _lite()
    assert entry["reduced"] == ["n_routed_experts"] == list(d["reduced"])
    assert d["n_routed_experts"] == 8 and d["n_routed_experts_published"] == 64
    cfg = arch.family_of(a).program_config(a)
    assert (cfg.moe.experts_held, cfg.moe.num_experts,
            cfg.moe.expert_offset) == (8, 64, 0)
    # every other size is the published one
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.vocab_size,
            cfg.moe.top_k, cfg.moe.expert_d_ff, cfg.d_ff) \
        == (27, 2048, 16, 102400, 6, 1408, 10944)
    assert Model(cfg).experts_held == (8, 64, 0)


@pytest.mark.parametrize("change", [
    {"routed_scaling_factor": 2.5}, {"q_lora_rank": 1536},
    {"rope_scaling": {"type": "linear", "factor": 4.0}}])
def test_parse_refuses_what_the_family_does_not_draw(change):
    _entry, d, a = _lite()
    with pytest.raises(arch.BenchError):
        arch.family_of(a).parse(dict(d, **change))


def test_the_engine_reports_the_share_it_holds():
    import jax
    import jax.numpy as jnp
    from repro.models.model import build_model
    from repro.serving import ChunkStore, RealServingEngine, Request
    a = arch.load(os.path.join(HERE, "tiny-mla-moe.json"))
    cfg = arch.family_of(a).program_config(a)
    m = build_model(cfg, param_dtype=jnp.float32)
    eng = RealServingEngine(
        m, m.init(jax.random.PRNGKey(0)), stages=2, chunk_size=8,
        kvstore=ChunkStore(chunk_size=4, quant="none", default_tier="host"),
        telemetry=True)
    rep = eng.serve([Request("r", 0.0, 12, 3, decode_len=2)], verify=True)
    gauges = rep.telemetry["metrics"]["gauges"]
    assert gauges["moe.experts_held{offset=4,total=16}"]["value"] == 4


@pytest.mark.parametrize("ctx", [1, 4096, 13000])
def test_work_counts_at_the_published_widths(ctx):
    _entry, _d, a = _lite()
    fam = arch.family_of(a)
    d, h = 2048, 16
    attn = 2 * (d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d) \
        + 2 * h * (192 + 128) * ctx
    dense = 2 * 3 * d * 10944
    # router, the 6 × 8/64 = 0.75 held experts a token selects on
    # average, and the 2 shared experts
    expert = 2 * d * 64 + 2 * 3 * d * 1408 * (0.75 + 2)
    assert fam.token_flops(a, ctx) == 27 * attn + dense + 26 * expert
    assert fam.head_flops(a) == 2 * d * 102400
    assert fam.kv_bytes_per_token_layer(a, 2) == 1152
    assert fam.kv_scale_bytes_per_chunk(a) == 576 * 4
