"""The architecture is a family found by name (``harness.arch``).

* The dense family reproduces what the harness computed before its code
  moved into ``bench/families/dense_gqa.py`` (``data/golden_dense_gqa.json``):
  the sha256 of every weight leaf, and of the float32 and fp8 reference
  logits at fixed rows of a fixed prompt; the FLOPs of three turns; and
  ``load_gbps`` of a fixed record of load ops, at both stored precisions.
* A configuration of a new family enters by files alone: a root that holds
  a copy of ``BENCHMARK.json`` with one more cell, and the cell's
  configuration, family, traffic and limits, runs through ``run.load_cell``
  and ``run.run_cell`` to a correct result, reaches every entry point of
  its family, and writes nothing in the checkout.
* A configuration that names a family with no file fails, naming the path.
"""
import hashlib
import json
import os
import re
import shutil
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import run
from harness import arch, flops, peaks, readers, reference, weights

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "golden_dense_gqa.json")) as _f:
    GOLDEN = json.load(_f)
PROMPT_LEN = 40
LOAD_OPS = [
    SimpleNamespace(kind="load", from_reference=False, tokens=(0, 700),
                    layers=(0, 9), t0=1.0, t1=1.25),
    SimpleNamespace(kind="load", from_reference=True, tokens=(0, 512),
                    layers=(0, 9), t0=2.0, t1=2.5),
    SimpleNamespace(kind="compute", from_reference=False, tokens=(0, 512),
                    layers=(9, 18), t0=3.0, t1=3.5),
    SimpleNamespace(kind="load", from_reference=False, tokens=(512, 1300),
                    layers=(9, 18), t0=4.0, t1=4.125)]
ENTRY_POINTS = ("parse", "draw", "program_params", "program_config",
                "layers", "token_flops", "head_flops",
                "kv_bytes_per_token_layer", "kv_scale_bytes_per_chunk")


def _sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()


def _config(name: str) -> arch.Arch:
    for d in (os.path.join(run.BENCH, "configs"), HERE):
        path = os.path.join(d, name + ".json")
        if os.path.exists(path):
            return arch.load(path)
    raise FileNotFoundError(name)


def _ctx(a, quant: str, turns=()):
    return readers.Context(a, {"quant": quant}, list(turns),
                           SimpleNamespace(ops=LOAD_OPS), 1.0, 0,
                           peaks.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("name", ["tiny-qwen", "tiny-phi"])
def test_dense_weights_and_reference_logits_match_the_golden(name):
    g = GOLDEN["configs"][name]
    a = _config(name)
    w = weights.make_weights(a, GOLDEN["seed"])
    got = {jax.tree_util.keystr(p): _sha(x)
           for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == g["weights"]
    prompt = (np.arange(PROMPT_LEN, dtype=np.int32) * 37 + 11) % a.vocab
    for mode in ("f32", "fp8"):
        logits = reference.logits_at(w, a, prompt, GOLDEN["rows"], mode=mode)
        assert _sha(logits.astype(np.float32)) == g["logits"][mode], mode


@pytest.mark.parametrize("name", ["tiny-qwen", "tiny-phi", "qwen3-8b-18L",
                                  "phi4-mini-3.8b"])
def test_dense_work_counts_match_the_golden(name):
    g = GOLDEN["configs"][name]
    a = _config(name)
    assert [flops.turn_flops(a, *s) for s in GOLDEN["turn_shapes"]] \
        == g["turn_flops"]
    for quant, want in g.get("load_gbps", {}).items():
        assert readers.reader("load_gbps")(_ctx(a, quant)) == want, quant


COUNTED_FAMILY = '''"""The dense family under another name, counting the
calls of each entry point."""
from collections import Counter

from harness.arch import family

CALLS = Counter()
_dense = family("dense_gqa")


def _counted(name):
    def call(*args, **kw):
        CALLS[name] += 1
        return getattr(_dense, name)(*args, **kw)
    return call


for _name in %r:
    globals()[_name] = _counted(_name)
''' % (ENTRY_POINTS,)


def _root(tmp_path, family: str, source=None) -> str:
    """A root with a copy of BENCHMARK.json and one more cell,
    ``tiny-counted.decode``, whose configuration names ``family``; with
    ``source``, the family's file."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    for d in ("configs", "families", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-counted",
        "source": "https://huggingface.co/Qwen/Qwen3-8B",
        "file": "bench/configs/tiny-counted.json", "reduced": [],
        "why": "a configuration of a family added by files alone"})
    spec["workloads"].append({
        "name": "tiny-counted.decode", "config": "tiny-counted",
        "traffic": "tiny-counted", "chips": 1,
        "why": "a cell added by files alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    with open(os.path.join(HERE, "tiny-qwen.json")) as f:
        cfg = dict(json.load(f), name="tiny-counted", family=family)
    (bench / "configs" / "tiny-counted.json").write_text(json.dumps(cfg))
    if source is not None:
        (bench / "families" / (family + ".py")).write_text(source)
    shutil.copy(os.path.join(HERE, "tiny-host.json"),
                bench / "traffic" / "tiny-counted.json")
    with open(os.path.join(run.BENCH, "limits",
                           "qwen3-8b-18L.short-prefix-long-output.json")) as f:
        limits = dict(json.load(f), served_tokens=40)
    (bench / "limits" / "tiny-counted.decode.json").write_text(
        json.dumps(limits))
    return str(root)


def _checkout_files() -> dict:
    """Every file of the checkout with its size and time of change
    (Python's byte-code caches aside)."""
    out = {}
    for d, dirs, files in os.walk(run.ROOT):
        dirs[:] = [x for x in dirs if x not in (
            ".git", "__pycache__", ".pytest_cache", ".hypothesis")]
        for f in files:
            st = os.stat(os.path.join(d, f))
            out[os.path.join(d, f)] = (st.st_size, st.st_mtime_ns)
    return out


def test_a_new_family_enters_by_files_alone(tmp_path):
    root = _root(tmp_path, "counted", COUNTED_FAMILY)
    before = _checkout_files()
    c = run.load_cell("tiny-counted.decode", root)
    fam = arch.family_of(c["arch"])
    assert fam.__file__ == os.path.join(root, "bench", "families",
                                        "counted.py")
    r = run.run_cell(c, 2**31 + 29, 1.0, False, require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # what a traced run on the chip reads through the family
    turns = [SimpleNamespace(prefix=64, suffix=16, outputs=12)]
    for quant in ("none", "int8"):
        ctx = _ctx(c["arch"], quant, turns)
        assert readers.reader("mfu.decode")(ctx) > 0
        assert readers.reader("load_gbps")(ctx) > 0
    assert sorted(fam.CALLS) == sorted(ENTRY_POINTS)
    assert _checkout_files() == before


def test_a_missing_family_is_named(tmp_path):
    root = _root(tmp_path, "no_such_family")
    path = os.path.join(root, "bench", "families", "no_such_family.py")
    with pytest.raises(run.BenchError, match=re.escape(path)):
        run.load_cell("tiny-counted.decode", root)
