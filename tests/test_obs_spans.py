"""The in-program span recorder (``repro.obs.spans``) on the served path.

A tiny model served through ``RealServingEngine.serve`` on the CPU:

  * recording off records nothing and serves the same tokens as on;
  * on, the spans nest ``engine.run`` ⊃ ``decode.step`` ⊃ ``decode.token``
    ⊃ ``decode.readback``, one readback and one ``token`` stamp per token;
  * a compile becomes one ``compile`` span, named, under the span that
    triggered it;
  * under a profiler session the ``cacheflow/<name>`` annotations land in
    the ``.xplane.pb`` one for one with the log;
  * measured real-mode telemetry stamps on the host clock and carries the
    run's spans; restores from the device reference copy are counted;
  * the codelint ``span-catalog`` rule fires on an undeclared name.
"""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.codelint import check_span_catalog
from repro.configs import get_config
from repro.models import build_model
from repro.obs import SPAN_CATALOG, spans
from repro.serving import ChunkStore, RealServingEngine, Request


@pytest.fixture
def forced():
    """Recording forced on for one test, through the switch the
    ``CACHEFLOW_TELEMETRY`` opt-in sets; the log starts empty."""
    prev = spans.force_recording(True)
    spans.log().clear()
    try:
        yield spans.log()
    finally:
        spans.force_recording(prev)
        spans.log().clear()


@pytest.fixture
def unforced():
    prev = spans.force_recording(False)
    spans.log().clear()
    try:
        yield spans.log()
    finally:
        spans.force_recording(prev)
        spans.log().clear()


_MODEL = {}


def _model():
    if not _MODEL:
        cfg = get_config("qwen3-8b").reduced()
        m = build_model(cfg)
        _MODEL.update(model=m, params=m.init(jax.random.PRNGKey(0)))
    return _MODEL


def _requests():
    return [Request(f"r{i}", 0.0, 24 + 16 * i, 8, decode_len=3)
            for i in range(2)]


def _serve(*, store=True, telemetry=None):
    mm = _model()
    kv = ChunkStore(chunk_size=8, default_tier="host") if store else None
    eng = RealServingEngine(mm["model"], mm["params"], stages=2,
                            chunk_size=8, kvstore=kv, telemetry=telemetry)
    rep = eng.serve(_requests(), verify=True)
    tokens = {r: eng.executor.outputs(r)["tokens"] for r in ("r0", "r1")}
    return eng, rep, tokens


def _names(log):
    return [r[0] for r in log.records]


def _ancestors(log, i):
    out = []
    p = log.records[i][3]
    while p is not None:
        out.append(log.records[p][0])
        p = log.records[p][3]
    return out


@pytest.fixture(scope="module")
def served():
    """One recorded serve (first, so it compiles) and one unrecorded."""
    prev = spans.force_recording(True)
    spans.log().clear()
    try:
        _, rep_on, tok_on = _serve()
        records = [list(r) for r in spans.log().records]
        spans.force_recording(False)
        spans.log().clear()
        _, _, tok_off = _serve()
        n_off = len(spans.log().records)
    finally:
        spans.force_recording(prev)
        spans.log().clear()
    log = spans.SpanLog()
    log.records = records
    return {"log": log, "tok_on": tok_on, "tok_off": tok_off,
            "n_off": n_off}


def test_recording_off_records_nothing_and_serves_the_same(served):
    assert served["n_off"] == 0
    assert served["tok_on"] == served["tok_off"]
    assert spans.span("decode.step") is spans.span("prefill")  # shared null


def test_spans_nest_along_the_served_path(served):
    log = served["log"]
    names = _names(log)
    assert names.count("engine.run") == 1
    for i, name in enumerate(names):
        t0, t1 = log.records[i][1], log.records[i][2]
        assert t1 is not None and t1 >= t0, name
        p = log.records[i][3]
        if p is not None and name != "compile":
            # a child lies inside its parent on the span clock
            assert log.records[p][1] <= t0 and t1 <= log.records[p][2]
        if name == "decode.readback":
            assert _ancestors(log, i)[:3] == ["decode.token", "decode.step",
                                              "engine.run"]
        if name in ("restore.compute", "restore.load", "prefill",
                    "decode.step"):
            assert _ancestors(log, i) == ["engine.run"], name
    for name in set(names):
        assert name in SPAN_CATALOG, name


def test_each_token_has_one_readback_and_one_stamp(served):
    log = served["log"]
    names = _names(log)
    generated = sum(len(t) for t in served["tok_on"].values())
    decoded = generated - len(served["tok_on"])      # first tokens aside
    assert names.count("decode.token") == decoded
    assert names.count("decode.readback") == decoded
    assert names.count("prefill.readback") == len(served["tok_on"])
    tokens = [r for r in log.records if r[0] == "token"]
    assert len(tokens) == generated
    for rid, toks in served["tok_on"].items():
        idx = [r[5]["index"] for r in tokens if r[4] == rid]
        assert idx == list(range(len(toks)))
    # every decode.token holds exactly one readback
    for i, r in enumerate(log.records):
        if r[0] == "decode.token":
            kids = [c for c in log.records if c[3] == i]
            assert [c[0] for c in kids].count("decode.readback") == 1
            assert r[4] in ("r0", "r1")


def test_served_loads_say_where_their_bytes_came_from(served):
    loads = [r for r in served["log"].records if r[0] == "restore.load"]
    assert loads
    for r in loads:
        assert r[5]["from_reference"] is False
        assert r[5]["fetched"] >= 0


def test_a_new_shape_compiles_under_the_span_that_ran_it(forced):
    def fresh_double(x):
        return x * 2.0 + 1.0

    f = jax.jit(fresh_double)
    x = jnp.ones((3, 7, 5))
    spans.log().clear()
    with spans.span("decode.step", batch=1):
        with spans.span("decode.forward"):
            f(x).block_until_ready()
    names = _names(forced)
    comp = [i for i, n in enumerate(names) if n == "compile"]
    assert len(comp) == 1
    rec = forced.records[comp[0]]
    assert "fresh_double" in rec[5]["fun_name"]
    assert names[rec[3]] == "decode.forward"
    assert rec[5]["backend_s"] > 0 and rec[5]["lower_s"] > 0
    fwd = forced.records[rec[3]]
    # the compile's bounds, moved from time.time(), lie inside its parent
    # (the two clocks are read apart, so allow a millisecond)
    assert fwd[1] - 1e6 <= rec[1] <= rec[2] <= fwd[2] + 1e6
    # the same shape again: no compile
    with spans.span("decode.forward"):
        f(x).block_until_ready()
    assert _names(forced).count("compile") == 1


def test_profiler_session_records_annotations_one_for_one(unforced,
                                                          tmp_path):
    assert not spans.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert spans.recording()
        _serve()
    assert not spans.recording()
    steps = _names(unforced).count("decode.step")
    assert steps > 0
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    seen = sum(1 for plane in data.planes for line in plane.lines
               for e in line.events if e.name == "cacheflow/decode.step")
    assert seen == steps


def test_log_drops_past_its_cap():
    log = spans.SpanLog(cap=3)
    for i in range(5):
        log.instant("token", "r", {"index": i})
    assert len(log.records) == 3 and log.dropped == 2
    assert log.open("decode.step", None, {}) is None
    assert log.dropped == 3


def test_real_telemetry_is_on_the_host_clock_and_carries_spans(unforced):
    _, rep, tokens = _serve(telemetry=True)
    snap = rep.telemetry
    assert snap["clock"]["kind"] == "host"
    names = [s["name"] for s in snap["spans"]]
    assert names[0] == "engine.run" and snap["spans"][0]["parent"] is None
    assert names.count("token") == sum(len(t) for t in tokens.values())
    run = snap["spans"][0]
    run_s = (run["t1_ns"] - run["t0_ns"]) * 1e-9
    for rid, edges in snap["phases"].items():
        ts = [t for t, _ in edges]
        assert ts == sorted(ts) and 0 <= ts[0] and ts[-1] <= run_s
    hist = snap["metrics"]["histograms"]["engine.ttft_seconds"]
    assert hist["count"] == 2 and 0 < hist["sum"] <= 2 * run_s
    # the per-engine opt-in recorded for its run only
    assert not spans.recording()


def test_reference_restores_are_counted(unforced):
    # without a chunk store every load op copies from the reference
    eng, rep, _ = _serve(store=False, telemetry=True)
    loads = [s for s in rep.telemetry["spans"] if s["name"] == "restore.load"]
    assert loads and all(s["attrs"]["from_reference"] for s in loads)
    n = eng.executor.reference_restores
    assert n == len(loads)
    counters = rep.telemetry["metrics"]["counters"]
    assert counters["datapath.reference_restores_total"] == n


def test_codelint_mutation_span_catalog(tmp_path):
    reg = tmp_path / "registry.py"
    reg.write_text('SPAN_CATALOG = {"decode.step": {"layer": "x"}}\n')
    mod = tmp_path / "mod.py"
    mod.write_text("from repro.obs import spans\n"
                   "from repro.obs.spans import instant, span\n"
                   "def f():\n"
                   "    with span('decode.step'):\n"
                   "        instant('decode.ghost')\n"
                   "    with spans.span('decode.phantom', rid='r'):\n"
                   "        pass\n")
    found = check_span_catalog(reg, [mod])
    assert [f.rule for f in found] == ["span-catalog"] * 2
    assert {"decode.ghost", "decode.phantom"} == {
        f.message.split("'")[1] for f in found}
    ok = tmp_path / "ok.py"
    ok.write_text("def f(name, span):\n"
                  "    with span('decode.step'):\n"
                  "        span(name)\n")
    assert check_span_catalog(reg, [ok]) == []
    reg.write_text("SPAN_CATALOG = build()\n")
    assert [f.rule for f in check_span_catalog(reg, [ok])] \
        == ["span-catalog"]


def test_codelint_repo_span_literals_all_declared():
    from repro.analysis.codelint import run_all
    import repro.analysis
    from pathlib import Path
    root = Path(repro.analysis.__file__).resolve().parents[3]
    found = [f for f in run_all(root) if f.rule == "span-catalog"]
    assert found == [], [str(f) for f in found]


def test_codelint_mutation_scope_catalog(tmp_path):
    from repro.analysis.codelint import check_scope_catalog
    reg = tmp_path / "registry.py"
    reg.write_text('SCOPE_CATALOG = {"moe": {"layer": "x"}}\n')
    mod = tmp_path / "mod.py"
    mod.write_text("import jax\n"
                   "from jax import named_scope\n"
                   "def f():\n"
                   "    with jax.named_scope('moe'):\n"
                   "        pass\n"
                   "    with named_scope('ghost'):\n"
                   "        pass\n")
    found = check_scope_catalog(reg, [mod])
    assert [f.rule for f in found] == ["scope-catalog"]
    assert "'ghost'" in found[0].message
    reg.write_text("SCOPE_CATALOG = build()\n")
    assert [f.rule for f in check_scope_catalog(reg, [mod])] \
        == ["scope-catalog"]


def test_codelint_repo_scope_literals_all_declared():
    from repro.analysis.codelint import run_all
    import repro.analysis
    from pathlib import Path
    from repro.obs import SCOPE_CATALOG
    root = Path(repro.analysis.__file__).resolve().parents[3]
    found = [f for f in run_all(root) if f.rule == "scope-catalog"]
    assert found == [], [str(f) for f in found]
    assert set(SCOPE_CATALOG) == {"moe", "mla"}
