"""CacheFlow sanitizer, trace linter and codelint (DESIGN.md §14).

Three layers of self-test:

  * **Fuzz**: randomized mixed interleavings (preempt + evict + prefetch +
    channel failure + fork-style CoW) run under ``sanitize=True`` — the
    sanitizer must stay silent on correct engine behavior, and every
    captured trace must lint clean.
  * **Mutation**: for every sanitizer invariant class, every trace-lint
    rule and every codelint rule, a deliberately broken input must trigger
    exactly that detector (a checker that can't fail its mutant is dead
    code).
  * **Regression**: the PlacementCore demote-cascade double-count (a
    bottom-tier drop previously counted as a demotion AND a drop) and the
    sanitized serving report plumbing.
"""
import copy

import numpy as np
import pytest

from _engine_helpers import RngBackend
from hypothesis import given, settings, strategies as st

from repro.analysis.codelint import (check_at_set_loops,
                                     check_kernel_oracles,
                                     check_trace_kinds, check_unseeded_rng,
                                     run_all)
from repro.analysis.sanitizer import EngineSanitizer, SanitizerViolation
from repro.analysis.trace_lint import ALL_RULES, lint_trace
from repro.config import HARDWARE, IO_BANDWIDTHS
from repro.configs import get_config
from repro.core import EngineCore, EngineRequest
from repro.core.baselines import make_baseline_plans
from repro.core.trace import ScheduleTrace, TraceEvent, TraceRecorder
from repro.serving import Request, SimServingEngine, TieredKVStore
from repro.storage import PlacementCore, Tier


# ---------------------------------------------------------------------------
# Direct-hook harness for the runtime sanitizer
# ---------------------------------------------------------------------------


class _Op:
    """Minimal op stub: the sanitizer hooks only read these fields."""

    def __init__(self, kind, rid, stage=0, unit=0):
        self.kind = kind
        self.request_id = rid
        self.stage = stage
        self.unit = unit


class _Core:
    def __init__(self, max_active=0, kvstore=None):
        self.max_active = max_active
        self.kvstore = kvstore


def _san(max_active=0, kvstore=None):
    san = EngineSanitizer(_Core(max_active=max_active, kvstore=kvstore))
    san.bind(ops_log=[], busy_comp={0: 0.0}, busy_io={0: 0.0})
    return san


def _mk_req(rid, n=32, **kw):
    plans = make_baseline_plans("cacheflow", rid, n, chunk_size=8,
                                l_delta=0, num_layers=4)
    return EngineRequest(rid, n, 0.0, plans, **kw)


def test_mutation_double_claim_both_pointers():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    san.on_dispatch(0.0, "comp0", _Op("compute", "r0", 0, 2), 1.0)
    with pytest.raises(SanitizerViolation, match="double-claim"):
        san.on_dispatch(0.0, "io0", _Op("load", "r0", 0, 2), 1.0)


def test_mutation_channel_double_occupancy():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    san.on_dispatch(0.0, "comp0", _Op("compute", "r0", 0, 0), 1.0)
    with pytest.raises(SanitizerViolation, match="channel-occupancy"):
        san.on_dispatch(0.0, "comp0", _Op("compute", "r0", 0, 1), 1.0)


def test_mutation_double_restore():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    op = _Op("load", "r0", 0, 3)
    san.on_dispatch(0.0, "io0", op, 1.0)
    san.on_complete(1.0, "io0", op)
    with pytest.raises(SanitizerViolation, match="double-restore"):
        san.on_dispatch(1.0, "io0", _Op("load", "r0", 0, 3), 1.0)


def test_mutation_inexact_completion_time():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    op = _Op("load", "r0", 0, 3)
    san.on_dispatch(0.0, "io0", op, 1.0)
    with pytest.raises(SanitizerViolation, match="completion-time"):
        san.on_complete(1.0 + 1e-12, "io0", op)


def test_mutation_virtual_time_regression():
    san = _san()
    san.on_event(2.0, "comp_done")
    with pytest.raises(SanitizerViolation, match="time-monotonic"):
        san.on_event(1.5, "io_done")


def test_mutation_negative_duration_and_inactive_dispatch():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    with pytest.raises(SanitizerViolation, match="negative-duration"):
        san.on_dispatch(0.0, "io0", _Op("load", "r0", 0, 3), -0.5)
    san = _san()
    with pytest.raises(SanitizerViolation, match="inactive-dispatch"):
        san.on_dispatch(0.0, "io0", _Op("load", "ghost", 0, 3), 0.5)


def test_mutation_slot_overflow_and_double_admit():
    san = _san(max_active=1)
    san.on_admit(0.0, _mk_req("r0"))
    with pytest.raises(SanitizerViolation, match="slot-overflow"):
        san.on_admit(0.0, _mk_req("r1"))
    san = _san(max_active=4)
    san.on_admit(0.0, _mk_req("r0"))
    with pytest.raises(SanitizerViolation, match="slot-conservation"):
        san.on_admit(0.0, _mk_req("r0"))


def test_mutation_finish_and_resume_of_inactive():
    san = _san()
    with pytest.raises(SanitizerViolation, match="slot-conservation"):
        san.on_finish(0.0, "never-admitted")
    san = _san()
    with pytest.raises(SanitizerViolation, match="slot-conservation"):
        san.on_resume(0.0, "never-suspended")


def test_mutation_restore_incomplete():
    san = _san()
    req = _mk_req("r0", n=32)            # 4 units of 8 tokens
    san.on_admit(0.0, req)
    op = _Op("load", "r0", 0, 3)
    san.on_dispatch(0.0, "io0", op, 1.0)
    san.on_complete(1.0, "io0", op)
    with pytest.raises(SanitizerViolation, match="restore-incomplete"):
        san.on_restore_done(1.0, "r0")   # 3 units never completed


def test_mutation_rollback_drift_detected_at_run_end():
    san = _san()
    san.on_admit(0.0, _mk_req("r0"))
    op = _Op("load", "r0", 0, 3)
    san.on_dispatch(0.0, "io0", op, 1.0)
    san.on_complete(1.0, "io0", op)
    busy_comp, busy_io = san._engine_busy
    busy_io[0] += 0.25        # engine accounting drifts off the mirror
    with pytest.raises(SanitizerViolation, match="rollback-exact"):
        san.on_run_end(active=set(), pending=[], suspended=set())


def test_mutation_store_audit_drift():
    class _BadStore:
        def audit(self):
            raise AssertionError("host: used 512 != sum 256")

    san = _san(kvstore=_BadStore())
    with pytest.raises(SanitizerViolation, match="store-audit"):
        san.on_run_end(active=set(), pending=[], suspended=set())


def test_mutation_trace_schema_unregistered_kind():
    san = _san()
    with pytest.raises(SanitizerViolation, match="trace-schema"):
        san.on_trace_event(TraceEvent(kind="warp_core_breach", t=0.0))


# -- CoW parent-bytes check -------------------------------------------------


class _FakePool:
    """Dict-backed pool with a controllable copy(); mimics BlockPool's
    read/copy/refcounts surface."""

    def __init__(self, mutate_parent=False, diverge_copy=False):
        self._data = {0: {"k": np.arange(8.0)}}
        self.refcounts = [1]
        self.mutate_parent = mutate_parent
        self.diverge_copy = diverge_copy

    def read(self, bid):
        return self._data[bid]

    def copy(self, bid):
        new = max(self._data) + 1
        self._data[new] = {f: a.copy() for f, a in self._data[bid].items()}
        self.refcounts.append(1)
        if self.mutate_parent:
            self._data[bid]["k"][0] = 999.0
        if self.diverge_copy:
            self._data[new]["k"][1] = -999.0
        return new


class _PoolStore:
    def __init__(self, pool):
        self.pool = pool

    def audit(self):
        pass


def test_mutation_cow_parent_mutated():
    san = _san(kvstore=_PoolStore(_FakePool(mutate_parent=True)))
    with pytest.raises(SanitizerViolation, match="cow-parent-mutated"):
        san.core.kvstore.pool.copy(0)


def test_mutation_cow_copy_diverged():
    san = _san(kvstore=_PoolStore(_FakePool(diverge_copy=True)))
    with pytest.raises(SanitizerViolation, match="cow-copy-diverged"):
        san.core.kvstore.pool.copy(0)


def test_cow_check_passes_on_honest_pool_and_unwraps_at_run_end():
    pool = _FakePool()
    san = _san(kvstore=_PoolStore(pool))
    wrapped = pool.copy
    assert pool.copy(0) == 1             # wrapped, passes
    assert san.counters.cow_checks == 1
    san.on_run_end(active=set(), pending=[], suspended=set())
    assert pool.copy is not wrapped      # original restored


# ---------------------------------------------------------------------------
# Fuzz: mixed interleavings must sanitize silently and lint clean
# ---------------------------------------------------------------------------


class _FuzzBackend(RngBackend):
    def prefetch_secs(self, op, req, bandwidth):
        return float(self.rng.uniform(0.05, 1.0))

    def prefetch_gate(self, req):
        return True


@pytest.mark.property
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzz_sanitizer_silent_and_traces_lint_clean(seed):
    """Random preempt+evict+prefetch+channel-failure interleavings: the
    sanitizer must not fire on correct engine behavior, and the captured
    schedule must pass every offline lint rule."""
    rng = np.random.default_rng(seed)
    stages = int(rng.integers(1, 3))
    bounds = [(0, 2), (2, 4)] if stages == 2 else None
    policy = ["none", "priority", "deadline"][int(rng.integers(0, 3))]
    evict = policy != "none" and bool(rng.integers(0, 2))
    prefetch = bool(rng.integers(0, 2))
    io_channels = int(rng.integers(1, 3))
    kvstore = TieredKVStore() if (prefetch or rng.integers(0, 2)) else None
    fail = ({int(rng.integers(0, io_channels)): float(rng.uniform(0.5, 3.0))}
            if int(rng.integers(0, 3)) == 0 else None)
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
    rec = TraceRecorder()
    core = EngineCore(_FuzzBackend(seed), stages=stages,
                      io_channels=io_channels,
                      max_active=int(rng.integers(1, 4)),
                      preempt=policy, evict=evict, prefetch=prefetch,
                      kvstore=kvstore, channel_fail_at=fail,
                      sanitize=True, strict=True)
    core.run(reqs, trace=rec)
    san = core.last_sanitizer
    assert san is not None
    assert san.counters.admits >= len(reqs)
    assert san.counters.finishes == len(reqs)
    # hard invariants only: the starvation rule is an advisory heuristic
    # and adversarial workloads (channel failure + max_active=1) can
    # legitimately stall one request for over half the span
    findings = lint_trace(rec.trace,
                          rules=[r for r in ALL_RULES if r != "starvation"])
    assert not findings, [str(f) for f in findings[:5]]


# ---------------------------------------------------------------------------
# Trace linter: clean baseline + one mutant per rule
# ---------------------------------------------------------------------------


def _base_trace():
    reqs = [_mk_req(f"r{i}", n=32 + 16 * i, new_len=16, decode_len=2,
                    priority=i % 2)
            for i in range(4)]
    rec = TraceRecorder()
    EngineCore(RngBackend(11), stages=1, io_channels=2, max_active=2,
               preempt="priority", strict=True).run(reqs, trace=rec)
    return rec.trace


BASE = _base_trace()


def _mutant():
    return copy.deepcopy(BASE)


def _rules(findings):
    return {f.rule for f in findings}


def test_lint_base_trace_clean_and_roundtrips():
    assert lint_trace(BASE) == []
    # dict round-trip (what the CLI loads) is equally clean
    t = ScheduleTrace.from_dict(BASE.to_dict())
    assert lint_trace(t, raw_version=BASE.version) == []


def test_lint_mutation_schema_unknown_kind_and_missing_field():
    t = _mutant()
    next(e for e in t.events if e.kind == "admit").kind = "warp"
    assert "schema" in _rules(lint_trace(t))
    t = _mutant()
    next(e for e in t.events if e.kind == "dispatch").op = None
    assert "schema" in _rules(lint_trace(t))


def test_lint_mutation_schema_version_aware():
    t = _mutant()
    # a v3 event kind inside a trace claiming schema v1
    assert "schema" in _rules(lint_trace(t, raw_version=1))
    assert "schema" not in _rules(lint_trace(t, raw_version=5))


def test_lint_mutation_causality_time_regression():
    t = _mutant()
    t.events[len(t.events) // 2].t = -1.0
    assert "causality" in _rules(lint_trace(t))


def test_lint_mutation_causality_wrong_completion_time():
    t = _mutant()
    ev = next(e for e in t.events
              if e.kind == "complete" and e.op["kind"] in ("compute", "load"))
    ev.t += 1e-9
    assert "causality" in _rules(lint_trace(t))


def test_lint_mutation_channel_overlap():
    t = _mutant()
    d = next(e for e in t.events if e.kind == "dispatch")
    dup = copy.deepcopy(d)
    dup.op = dict(dup.op)
    t.events.insert(t.events.index(d) + 1, dup)
    assert "channel-overlap" in _rules(lint_trace(t))


def test_lint_mutation_slot_leak_dropped_finish():
    t = _mutant()
    fin = next(e for e in t.events if e.kind == "finish")
    t.events.remove(fin)
    assert "slot-leak" in _rules(lint_trace(t))


def test_lint_mutation_restored_twice():
    t = _mutant()
    ev = next(e for e in t.events
              if e.kind == "complete" and e.op["kind"] in ("compute", "load"))
    d = copy.deepcopy(next(e for e in t.events if e.kind == "dispatch"
                           and e.op == ev.op))
    c = copy.deepcopy(ev)
    i = t.events.index(ev) + 1
    d.t = c.t = t.events[i].t if i < len(t.events) else ev.t
    d.duration = 0.0
    t.events[i:i] = [d, c]
    assert "causality" in _rules(lint_trace(t))


# -- hand-crafted traces for gate-inversion / starvation / prefetch-race ----


def _plan_d(rid, n_tokens, stage=0):
    return {"request_id": rid, "n_tokens": n_tokens, "chunk_size": 8,
            "strategy": "token", "layer_lo": 0, "layer_hi": 4,
            "stage": stage, "comp_enabled": True, "io_enabled": True}


def _op_d(kind, rid, unit, stage=0):
    return {"kind": kind, "request_id": rid, "stage": stage, "unit": unit,
            "tokens": [0, 8], "layers": [0, 4]}


def _craft(events, requests, meta=None):
    base = {"max_active": 4, "evict": False,
            "io_policy": "longest_remaining", "stage_parallel": True}
    base.update(meta or {})
    return ScheduleTrace(meta=base, requests=requests,
                         events=[TraceEvent(**e) for e in events])


def test_lint_gate_inversion_skipped_better_candidate():
    reqs = [{"request_id": "big", "plans": [_plan_d("big", 64)]},
            {"request_id": "small", "plans": [_plan_d("small", 16)]}]
    ev = [dict(kind="admit", t=0.0, request_id="big"),
          dict(kind="admit", t=0.0, request_id="small"),
          # "small" (1 unit remaining fewer tokens, admitted later) loads
          # while "big" — strictly better under longest_remaining — was
          # never gated this pass: inversion
          dict(kind="dispatch", t=0.0, resource="io0",
               op=_op_d("load", "small", 1), duration=1.0)]
    assert "gate-inversion" in _rules(lint_trace(_craft(ev, reqs)))
    # a recorded gate=False for "big" justifies the skip
    ev_ok = ev[:2] + [dict(kind="gate", t=0.0, request_id="big", stage=0,
                           unit=7, allowed=False)] + ev[2:]
    assert lint_trace(_craft(ev_ok, reqs)) == []
    # gate=True AND skipped => benefit-gate inversion
    ev_bad = ev[:2] + [dict(kind="gate", t=0.0, request_id="big", stage=0,
                            unit=7, allowed=True)] + ev[2:]
    assert "gate-inversion" in _rules(lint_trace(_craft(ev_bad, reqs)))


def test_lint_starvation_window():
    reqs = [{"request_id": "fed", "plans": [_plan_d("fed", 64)]},
            {"request_id": "starved", "plans": [_plan_d("starved", 64)]}]
    ev = [dict(kind="admit", t=0.0, request_id="fed"),
          dict(kind="admit", t=0.0, request_id="starved")]
    t = 0.0
    for u in range(7, 1, -1):      # "fed" gets every dispatch for 6 units
        ev.append(dict(kind="dispatch", t=t, resource="io0",
                       op=_op_d("load", "fed", u), duration=2.0))
        t += 2.0
        ev.append(dict(kind="complete", t=t, resource="io0",
                       op=_op_d("load", "fed", u)))
    trace = _craft(ev, reqs)
    assert "starvation" in _rules(lint_trace(trace, starvation_bound=3.0,
                                             rules=["starvation"]))
    assert lint_trace(trace, starvation_bound=100.0,
                      rules=["starvation"]) == []


def test_lint_prefetch_race_misaccounting():
    reqs = [{"request_id": "q", "plans": [_plan_d("q", 16)]}]
    pf = _op_d("prefetch", "q", 0, stage=-1)
    race = [dict(kind="prefetch_gate", t=0.0, request_id="q", allowed=True),
            dict(kind="dispatch", t=0.0, resource="io0", op=pf,
                 duration=5.0),
            # admitted mid-prefetch with NO abort recorded, and the
            # transfer then "completes" anyway: the race the engine's
            # cancel-at-admit path must make impossible
            dict(kind="admit", t=2.0, request_id="q"),
            dict(kind="complete", t=5.0, resource="io0", op=dict(pf))]
    assert "prefetch-race" in _rules(lint_trace(_craft(race, reqs)))
    ok = [race[0], race[1],
          dict(kind="abort", t=2.0, resource="io0", op=dict(pf)),
          dict(kind="admit", t=2.0, request_id="q")]
    assert "prefetch-race" not in _rules(lint_trace(_craft(ok, reqs)))
    # a prefetch dispatched without a passing gate is also a race bug
    nogate = [dict(kind="dispatch", t=0.0, resource="io0", op=dict(pf),
                   duration=5.0)]
    assert "prefetch-race" in _rules(lint_trace(_craft(nogate, reqs)))


def test_golden_traces_lint_clean():
    """Every captured trace committed under tests/data/ stays lint-clean
    (and exercises the file-loading path the CLI uses, including raw
    schema-version extraction)."""
    from repro.analysis.trace_lint import lint_trace_file
    data = _repo_root() / "tests" / "data"
    traces = sorted(data.glob("*trace*.json"))
    assert traces, "no golden traces committed under tests/data/"
    for p in traces:
        findings = lint_trace_file(p)
        assert not findings, (p.name, [str(f) for f in findings[:5]])


def test_lint_cli_exit_codes(tmp_path):
    from repro.analysis.lint_trace import main
    golden = sorted((_repo_root() / "tests" / "data").glob("*trace*.json"))
    assert main([str(golden[0])]) == 0
    import json
    d = json.loads(golden[0].read_text())
    d["events"][3]["kind"] = "warp"
    bad = tmp_path / "bad_trace.json"
    bad.write_text(json.dumps(d))
    assert main([str(bad)]) == 1
    assert main([str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# codelint: repo is clean; one mutant per rule
# ---------------------------------------------------------------------------


def _repo_root():
    # repro is a namespace package (__file__ is None); anchor on a real one
    import repro.analysis
    from pathlib import Path
    return Path(repro.analysis.__file__).resolve().parents[3]


def test_codelint_repo_is_clean():
    assert run_all(_repo_root()) == []


def test_codelint_mutation_at_set_loop(tmp_path):
    bad = tmp_path / "hot.py"
    bad.write_text("for i in range(4):\n"
                   "    cache = cache.at[i].set(x)\n")
    findings = check_at_set_loops([bad])
    assert [f.rule for f in findings] == ["at-set-loop"]
    bad.write_text("for i in range(4):\n"
                   "    cache = cache.at[i].set(x)  "
                   "# codelint: allow(at-set-loop)\n")
    assert check_at_set_loops([bad]) == []
    # pragma on the loop header covers the whole loop
    bad.write_text("for i in range(4):  # codelint: allow(at-set-loop)\n"
                   "    cache = cache.at[i].set(x)\n")
    assert check_at_set_loops([bad]) == []
    # out of a loop: fine
    bad.write_text("cache = cache.at[0].set(x)\n")
    assert check_at_set_loops([bad]) == []


def test_codelint_mutation_unseeded_rng(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text("import time\nimport random\nimport numpy as np\n"
                   "a = time.time()\n"
                   "b = random.random()\n"
                   "c = np.random.default_rng()\n"
                   "d = np.random.normal()\n")
    rules = [f.rule for f in check_unseeded_rng([bad])]
    assert rules == ["unseeded-rng"] * 4
    ok = tmp_path / "ok.py"
    ok.write_text("import time\nimport numpy as np\n"
                  "a = time.perf_counter()\n"
                  "rng = np.random.default_rng(0)\n"
                  "b = rng.normal()\n")
    assert check_unseeded_rng([ok]) == []


def test_codelint_mutation_kernel_oracle(tmp_path):
    kdir = tmp_path / "kernels" / "myker"
    kdir.mkdir(parents=True)
    (kdir / "kernel.py").write_text("pass\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    findings = check_kernel_oracles(tmp_path / "kernels", tdir)
    assert sorted(f.rule for f in findings) == ["kernel-oracle"] * 2
    (kdir / "ref.py").write_text("pass\n")
    (tdir / "test_k.py").write_text(
        "def test_myker_interpret_parity(): pass\n")
    assert check_kernel_oracles(tmp_path / "kernels", tdir) == []


def test_codelint_mutation_trace_kinds(tmp_path):
    tr = tmp_path / "trace.py"
    tr.write_text('EVENT_KINDS = {"admit": 1}\n'
                  'def record(self, t):\n'
                  '    self._ev(kind="admit", t=t)\n'
                  '    self._ev(kind="vanish", t=t)\n')
    findings = check_trace_kinds(tr)
    assert [f.rule for f in findings] == ["trace-kinds"]
    assert "vanish" in findings[0].message
    tr.write_text('EVENT_KINDS = {"admit": 1}\n'
                  'def scan(e):\n'
                  '    return e.kind == "ghost"\n')
    assert [f.rule for f in check_trace_kinds(tr)] == ["trace-kinds"]


# ---------------------------------------------------------------------------
# Satellites: placement accounting fix + serving report plumbing
# ---------------------------------------------------------------------------


def test_placement_drop_from_bottom_is_not_a_demotion():
    core = PlacementCore([Tier("only", 1e9, 100)])
    core.put("a", "only", nbytes=80)
    core.put("b", "only", nbytes=80)   # evicts a -> falls off the bottom
    assert core.drops == 1
    assert core.demotions == 0         # previously double-counted
    core.audit()


def test_placement_demote_cascade_counts_each_landing_once():
    core = PlacementCore([Tier("top", 1e9, 100), Tier("bot", 1e8, 100)])
    core.put("a", "top", nbytes=80)
    core.put("b", "top", nbytes=80)    # a demotes to bot (lands)
    assert (core.demotions, core.drops) == (1, 0)
    core.put("c", "top", nbytes=80)    # b demotes, evicting a off the bottom
    assert (core.demotions, core.drops) == (2, 1)
    core.audit()


def test_serving_report_carries_sanitizer_counters(monkeypatch):
    # isolate from the ambient env (CI runs some suites with
    # CACHEFLOW_SANITIZE=1): this test pins the explicit-kwarg behavior
    monkeypatch.delenv("CACHEFLOW_SANITIZE", raising=False)
    cfg = get_config("qwen3-8b")
    reqs = [Request(f"r{i}", 0.2 * i, prefix_len=4096, new_len=128,
                    decode_len=2) for i in range(3)]
    eng = SimServingEngine(cfg, HARDWARE["h100"],
                           io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                           stages=2, max_batch=2, sanitize=True)
    rep = eng.run(reqs)
    assert rep.sanitizer is not None
    assert rep.sanitizer["admits"] == 3
    assert rep.sanitizer["finishes"] == 3
    assert rep.sanitizer["max_active"] <= 2
    # off by default: no counters attached, no sanitizer constructed
    rep2 = SimServingEngine(cfg, HARDWARE["h100"],
                            io_bandwidth=IO_BANDWIDTHS["10Gbps"],
                            stages=2, max_batch=2).run(
        [Request("s0", 0.0, prefix_len=4096, new_len=128, decode_len=2)])
    assert rep2.sanitizer is None


def test_engine_env_var_opt_in(monkeypatch):
    monkeypatch.setenv("CACHEFLOW_SANITIZE", "1")
    core = EngineCore(RngBackend(3), stages=1, io_channels=1)
    assert core.sanitize
    core.run([_mk_req("r0")])
    assert core.last_sanitizer is not None
    assert core.last_sanitizer.counters.finishes == 1
    monkeypatch.setenv("CACHEFLOW_SANITIZE", "0")
    assert not EngineCore(RngBackend(3), stages=1, io_channels=1).sanitize
