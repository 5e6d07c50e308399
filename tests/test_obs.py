"""CacheFlow observability layer (DESIGN.md §15).

Four layers of self-test:

  * **Golden timeline**: the committed preemption trace exports to valid
    Chrome trace-event JSON with stable event counts — through the library
    call AND the ``python -m repro.obs.timeline`` CLI the CI artifact step
    uses.  Strict-JSON is asserted (Perfetto rejects bare NaN tokens).
  * **Bit-identity**: a telemetry-enabled engine run is IDENTICAL to a
    disabled one on ``EngineResult`` and ``ops_log``, property-tested over
    randomized mixed interleavings (hooks are pure observers).
  * **Registry invariants**: catalog enforcement (unknown name / wrong
    type / label-schema drift all raise), counter monotonicity, the
    histogram ``count == sum(bucket_counts)`` conservation law.
  * **Mutation**: the codelint ``metric-catalog`` rule fires on an
    unregistered metric literal and on a deleted catalog, and stays silent
    on registered names (a checker that can't fail its mutant is dead
    code).  Plus the strict-JSON report plumbing (``percentiles`` of an
    empty set, ``emit_bench``).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _engine_helpers import RngBackend
from hypothesis import given, settings, strategies as st

from repro.analysis.codelint import check_metric_catalog
from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import EngineCore, EngineRequest
from repro.core.baselines import make_baseline_plans
from repro.core.trace import ScheduleTrace, result_to_dict
from repro.obs import (METRIC_CATALOG, MetricsRegistry, Telemetry,
                       trace_to_chrome)
from repro.serving import Request, SimServingEngine, TieredKVStore
from repro.serving.metrics import dumps_report, percentiles, sanitize_json


def _repo_root():
    import repro.analysis
    from pathlib import Path
    return Path(repro.analysis.__file__).resolve().parents[3]


GOLDEN = _repo_root() / "tests" / "data" / "golden_trace_preempt.json"


def _strict_loads(text: str):
    """json.loads that REJECTS the NaN/Infinity extensions — what an
    external consumer (Perfetto, jq) actually accepts."""
    def _no_const(tok):
        raise ValueError(f"non-standard JSON token {tok!r}")
    return json.loads(text, parse_constant=_no_const)


# ---------------------------------------------------------------------------
# Golden timeline export (library + CLI)
# ---------------------------------------------------------------------------


def _golden_doc():
    trace = ScheduleTrace.load(GOLDEN)
    return trace, trace_to_chrome(trace)


def test_golden_timeline_stable_counts_and_schema():
    trace, doc = _golden_doc()
    evs = doc["traceEvents"]
    ops = trace.result["ops_log"]
    aborted = sum(1 for e in ops if e[3].endswith(":aborted"))
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # one duration slice per non-aborted op, one instant per aborted op
    assert len(by_ph["X"]) == len(ops) - aborted
    assert len(by_ph["i"]) == aborted
    # every request with >= 2 lifecycle anchors gets exactly one flow
    # start and one flow finish; the golden trace covers all 8 requests
    assert len(by_ph["s"]) == len(by_ph["f"]) == len(trace.requests)
    # metadata: process_name + (thread_name, thread_sort_index) per track
    resources = doc["otherData"]["resources"]
    assert len(by_ph["M"]) == 1 + 2 * len(resources)
    assert "decode" in resources
    # counter tracks derived from trace events are present
    names = {e["name"] for e in by_ph["C"]}
    assert {"queue_depth", "active_requests"} <= names
    # schema: required keys per phase type
    for e in by_ph["X"]:
        assert {"ts", "dur", "pid", "tid", "name", "cat"} <= e.keys()
        assert e["dur"] >= 0
    for e in by_ph["i"]:
        assert e["s"] == "t" and e["name"].endswith(":aborted")
    for e in by_ph["s"] + by_ph["f"] + by_ph.get("t", []):
        assert "id" in e and e["cat"] == "lifecycle"
    assert all(e["bp"] == "e" for e in by_ph["f"])
    assert doc["displayTimeUnit"] == "ms"


def test_golden_timeline_is_strict_json():
    _, doc = _golden_doc()
    text = json.dumps(doc, allow_nan=False)   # raises on any NaN/Inf
    assert _strict_loads(text) == doc


def test_timeline_cli_offline_export(tmp_path):
    out = tmp_path / "golden.timeline.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_repo_root() / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.timeline", str(GOLDEN),
         "-o", str(out)],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    doc = _strict_loads(out.read_text())
    _, lib_doc = _golden_doc()
    assert len(doc["traceEvents"]) == len(lib_doc["traceEvents"])
    # default output path: <trace stem>.timeline.json next to the input
    assert "timeline" in proc.stderr


def test_timeline_reconstructs_ops_from_stripped_trace():
    """Traces without a captured result still render: slices come from the
    pinned dispatch/decode_step durations."""
    trace = ScheduleTrace.load(GOLDEN)
    trace.result = None
    doc = trace_to_chrome(trace)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert slices
    cats = {e["cat"] for e in slices}
    assert "decode" in cats and ("restore-io" in cats or "prefill" in cats)


# ---------------------------------------------------------------------------
# Bit-identity: telemetry on == telemetry off
# ---------------------------------------------------------------------------


class _FuzzBackend(RngBackend):
    def prefetch_secs(self, op, req, bandwidth):
        return float(self.rng.uniform(0.05, 1.0))

    def prefetch_gate(self, req):
        return True


def _fuzz_requests(rng, kvstore, stages):
    bounds = [(0, 2), (2, 4)] if stages == 2 else None
    reqs = []
    for i in range(int(rng.integers(3, 8))):
        n = int(rng.integers(16, 160))
        plans = make_baseline_plans("cacheflow", f"r{i}", n, chunk_size=8,
                                    l_delta=0, num_layers=4,
                                    stage_bounds=bounds)
        reqs.append(EngineRequest(
            f"r{i}", n, arrival=float(rng.uniform(0, 3.0)), plans=plans,
            new_len=int(rng.integers(0, 3)) * 16,
            decode_len=int(rng.integers(0, 5)),
            priority=int(rng.integers(0, 3)),
            deadline=float(rng.uniform(0.5, 20.0))))
        if kvstore is not None:
            kvstore.put(f"r{i}", n * 1024, tier="remote")
    return reqs


def _run_once(seed, *, telemetry):
    rng = np.random.default_rng(seed)
    stages = int(rng.integers(1, 3))
    policy = ["none", "priority", "deadline"][int(rng.integers(0, 3))]
    evict = policy != "none" and bool(rng.integers(0, 2))
    io_channels = int(rng.integers(1, 3))
    use_store = bool(rng.integers(0, 2))
    kvstore = TieredKVStore() if use_store else None
    fail = ({int(rng.integers(0, io_channels)): float(rng.uniform(0.5, 3.0))}
            if int(rng.integers(0, 3)) == 0 else None)
    reqs = _fuzz_requests(rng, kvstore, stages)
    core = EngineCore(_FuzzBackend(seed), stages=stages,
                      io_channels=io_channels,
                      max_active=int(rng.integers(1, 4)),
                      preempt=policy, evict=evict,
                      prefetch=use_store and bool(rng.integers(0, 2)),
                      kvstore=kvstore, channel_fail_at=fail,
                      telemetry=telemetry)
    res = core.run(reqs)
    return res, core


@pytest.mark.property
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzz_telemetry_is_bit_identical(seed):
    """The whole point of the hook design: enabling telemetry changes
    NOTHING about the schedule.  Same seed, same config, telemetry
    off vs on — EngineResult (ops_log included) must match exactly."""
    res_off, core_off = _run_once(seed, telemetry=False)
    res_on, core_on = _run_once(seed, telemetry=True)
    assert result_to_dict(res_off) == result_to_dict(res_on)
    assert res_off.ops_log == res_on.ops_log
    assert core_off.last_telemetry is None
    tel = core_on.last_telemetry
    assert tel is not None
    snap = tel.snapshot()
    cs = snap["metrics"]["counters"]
    # sanity: the collection actually observed the run
    assert cs["engine.admissions_total"] >= len(res_on.finish)
    assert set(snap["phases"]) == set(res_on.finish)
    # the snapshot itself is strict JSON
    _strict_loads(json.dumps(snap, allow_nan=False))


def test_telemetry_collects_lifecycle_and_busy(tmp_path):
    res, core = _run_once(7, telemetry=True)
    snap = core.last_telemetry.snapshot()
    m = snap["metrics"]
    # per-resource busy seconds equal the summed non-aborted slice widths
    for key, g in m["gauges"].items():
        if not key.startswith("engine.resource_busy_seconds"):
            continue
        resource = key.split("resource=", 1)[-1].rstrip("}")
        expect = sum(t1 - t0 for t0, t1, r, d in res.ops_log
                     if r == resource and not d.endswith(":aborted"))
        assert g["value"] == pytest.approx(expect)
    # every finished request walked arrive -> admit -> ... -> finish
    for rid, edges in snap["phases"].items():
        names = [p for _, p in edges]
        assert names[0] == "arrive" and names[-1] == "finish"
        assert "admit" in names
        ts = [t for t, _ in edges]
        assert ts == sorted(ts)
    # histograms conserve their observations
    for h in m["histograms"].values():
        assert h["count"] == sum(h["bucket_counts"])


def test_engine_env_var_opt_in(monkeypatch):
    monkeypatch.setenv("CACHEFLOW_TELEMETRY", "1")
    core = EngineCore(RngBackend(3), stages=1, io_channels=1)
    assert core.telemetry
    n = 32
    plans = make_baseline_plans("cacheflow", "r0", n, chunk_size=8,
                                l_delta=0, num_layers=4)
    core.run([EngineRequest("r0", n, 0.0, plans)])
    assert core.last_telemetry is not None
    monkeypatch.setenv("CACHEFLOW_TELEMETRY", "0")
    assert not EngineCore(RngBackend(3), stages=1, io_channels=1).telemetry


def test_serving_report_carries_telemetry(monkeypatch):
    monkeypatch.delenv("CACHEFLOW_TELEMETRY", raising=False)
    cfg = get_config("qwen3-8b")
    reqs = [Request(f"r{i}", 0.2 * i, prefix_len=4096, new_len=128,
                    decode_len=2) for i in range(3)]
    eng = SimServingEngine(cfg, HARDWARE["h100"],
                           io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                           stages=2, max_batch=2, telemetry=True)
    rep = eng.run(reqs)
    assert rep.telemetry is not None
    assert rep.telemetry["metrics"]["counters"]["engine.admissions_total"] == 3
    assert len(rep.telemetry["phases"]) == 3
    # off by default: no snapshot attached, no registry constructed
    rep2 = SimServingEngine(cfg, HARDWARE["h100"],
                            io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                            stages=2, max_batch=2).run(
        [Request("s0", 0.0, prefix_len=4096, new_len=128, decode_len=2)])
    assert rep2.telemetry is None


# ---------------------------------------------------------------------------
# Registry invariants
# ---------------------------------------------------------------------------


def test_registry_enforces_catalog():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        reg.counter("engine.warp_core_breaches")
    with pytest.raises(TypeError):
        reg.gauge("engine.admissions_total")       # declared a counter
    with pytest.raises(ValueError):
        reg.counter("engine.dispatches_total")     # missing the kind label
    with pytest.raises(ValueError):
        reg.counter("engine.admissions_total", kind="x")  # extra label
    # same (name, labels) cell -> same live instance
    a = reg.counter("engine.dispatches_total", kind="load")
    b = reg.counter("engine.dispatches_total", kind="load")
    assert a is b
    assert a is not reg.counter("engine.dispatches_total", kind="compute")


def test_counter_rejects_negative_and_gauge_series():
    reg = MetricsRegistry()
    c = reg.counter("engine.admissions_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("engine.queue_depth")
    g.set(3)                 # sample without timestamp: no series entry
    g.set(5, t=1.5)
    g.set(2, t=2.0)
    assert g.value == 2.0
    assert g.series == [(1.5, 5.0), (2.0, 2.0)]


@pytest.mark.property
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzz_histogram_conservation(seed):
    rng = np.random.default_rng(seed)
    reg = MetricsRegistry()
    h = reg.histogram("engine.ttft_seconds")
    values = rng.uniform(0.0, 200.0, size=int(rng.integers(1, 100)))
    for v in values:
        h.observe(float(v))
    assert h.count == len(values) == sum(h.bucket_counts)
    assert h.sum == pytest.approx(float(values.sum()))
    # bucket placement: first bound >= value (or the overflow slot)
    for v in values:
        idx = next((i for i, b in enumerate(h.bounds) if v <= b),
                   len(h.bounds))
        assert h.bucket_counts[idx] > 0


def test_catalog_is_well_formed():
    for name, spec in METRIC_CATALOG.items():
        assert spec["type"] in ("counter", "gauge", "histogram"), name
        assert isinstance(spec["labels"], tuple), name
        assert "layer" in spec, name
        if spec["type"] == "histogram":
            assert list(spec["buckets"]) == sorted(spec["buckets"]), name


# ---------------------------------------------------------------------------
# codelint metric-catalog rule: one mutant each way
# ---------------------------------------------------------------------------


def test_codelint_mutation_metric_catalog(tmp_path):
    reg = tmp_path / "registry.py"
    reg.write_text('METRIC_CATALOG = {"engine.x_total": {"type": "counter"}}\n')
    mod = tmp_path / "mod.py"
    mod.write_text("def f(self):\n"
                   "    self.registry.counter('engine.x_total').inc()\n"
                   "    self.registry.gauge('engine.ghost').set(1)\n")
    findings = check_metric_catalog(reg, [mod])
    assert [f.rule for f in findings] == ["metric-catalog"]
    assert "engine.ghost" in findings[0].message
    # registered-only file is clean; non-literal first args are skipped
    ok = tmp_path / "ok.py"
    ok.write_text("def f(self, name):\n"
                  "    self.registry.counter('engine.x_total').inc()\n"
                  "    self.registry.counter(name).inc()\n")
    assert check_metric_catalog(reg, [ok]) == []
    # a deleted catalog is itself a finding
    reg.write_text("METRIC_CATALOG = build()\n")
    assert [f.rule for f in check_metric_catalog(reg, [ok])] \
        == ["metric-catalog"]


def test_codelint_repo_metric_literals_all_registered():
    from repro.analysis.codelint import run_all
    findings = [f for f in run_all(_repo_root())
                if f.rule == "metric-catalog"]
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# Strict-JSON report plumbing (percentiles / emit_bench satellites)
# ---------------------------------------------------------------------------


def test_percentiles_empty_is_null_not_nan():
    out = percentiles([])
    assert set(out) == {"p50", "p90", "p99", "mean"}
    assert all(v is None for v in out.values())
    # and it round-trips as strict JSON
    assert _strict_loads(dumps_report(out)) == {k: None for k in out}


def test_dumps_report_scrubs_non_finite():
    doc = {"a": float("nan"), "b": [1.0, float("inf")],
           "c": {"d": float("-inf"), "e": 2.0}, "f": "NaN-as-string"}
    text = dumps_report(doc)
    assert _strict_loads(text) == {"a": None, "b": [1.0, None],
                                   "c": {"d": None, "e": 2.0},
                                   "f": "NaN-as-string"}
    assert sanitize_json((1.0, float("nan"))) == [1.0, None]


def test_emit_bench_writes_repo_root_and_results(tmp_path):
    sys.path.insert(0, str(_repo_root()))
    try:
        from benchmarks.common import RESULTS, emit_bench
    finally:
        sys.path.pop(0)
    path = emit_bench("obs_selftest", {"v": float("nan"), "n": 3},
                      root=str(tmp_path))
    try:
        assert path == str(tmp_path / "BENCH_obs_selftest.json")
        doc = _strict_loads(open(path).read())
        assert doc == {"v": None, "n": 3}
        mirror = os.path.join(RESULTS, "BENCH_obs_selftest.json")
        assert _strict_loads(open(mirror).read()) == doc
    finally:
        os.unlink(os.path.join(RESULTS, "BENCH_obs_selftest.json"))
