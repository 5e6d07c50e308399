"""The program's spans on the device trace's clock, on synthetic intervals:
the clock offset from the harness's paired spans, the gap attribution, and
programs per decoded token."""
import random

import pytest

from harness import program_spans as ps
from harness.probe import DecodeSpan, OpSpan, Record
from harness.readers import Context
from harness.trace import DeviceTrace

OFFSET = 7.25e12          # trace ns = perf ns + OFFSET


def _paired(n_ops=8, n_decodes=8, jitter_ns=0.0, seed=0):
    """Harness spans on the trace clock and the probe's record of the same
    calls on ``perf_counter`` seconds, ``jitter_ns`` apart at most."""
    rng = random.Random(seed)
    rec, spans = Record(), []
    t = 1.0
    for i in range(n_ops + n_decodes):
        j = rng.uniform(-jitter_ns, jitter_ns)
        start = t * 1e9 + OFFSET + j
        if i < n_ops:
            rec.ops.append(OpSpan("compute", "r", t, t + 0.01, (0, 8),
                                  (0, 1)))
            spans.append((start, start + 1e7, "bench:compute"))
        else:
            rec.decodes.append(DecodeSpan(t, t + 0.01, ("r",)))
            spans.append((start, start + 1e7, "bench:decode"))
        t += 0.02
    return spans, rec


def test_offset_is_recovered_under_jitter():
    spans, rec = _paired(jitter_ns=50e3)
    pairs = ps.clock_pairs(spans + [(0, 1e15, "bench:window")], rec)
    assert len(pairs) == 16
    off, resid = ps.offset(pairs)
    assert off == pytest.approx(OFFSET, abs=50e3)
    assert 0 < resid <= 100e3


def test_no_alignment_from_too_few_pairs_or_a_wide_residual():
    spans, rec = _paired(n_ops=4, n_decodes=4)
    assert ps.offset(ps.clock_pairs(spans, rec)) is None       # 8 pairs
    spans, rec = _paired()
    # a kind whose counts differ on the two sides gives no pairs
    assert len(ps.clock_pairs(spans[1:], rec)) == 8
    # one harness span 0.6 ms off: the residual refuses the alignment
    s0 = spans[0]
    bad = [(s0[0] + 0.6e6, s0[1], s0[2])] + spans[1:]
    off, resid = ps.offset(ps.clock_pairs(bad, rec))
    assert resid > ps.MAX_RESIDUAL_NS
    assert ps.attribute([], [], bad, rec, 0, 1) is None


def _program():
    """A program log (perf ns): an engine run holding a prefill with a
    compile, then a decode step of two tokens."""
    E, P, S = 0, 1, 3
    return [
        ["engine.run", 0, 1000, None, None, {"requests": 2}],        # 0
        ["prefill", 100, 300, E, "r0", {}],                           # 1
        ["compile", 150, 250, P, None, {"fun_name": "jit(f)"}],       # 2
        ["decode.step", 400, 800, E, None, {"batch": 2}],             # 3
        ["decode.token", 400, 600, S, "r0", {}],                      # 4
        ["decode.forward", 420, 480, 4, None, {}],                    # 5
        ["decode.readback", 500, 600, 4, None, {}],                   # 6
        ["token", 600, 600, 4, "r0", {"index": 1}],                   # 7
        ["decode.token", 600, 800, S, "r1", {}],                      # 8
        ["compile", 610, 700, 8, None, {"fun_name": "jit(g)"}],       # 9
        ["decode.readback", 700, 800, 8, None, {}],                   # 10
    ]


def test_gaps_go_to_the_innermost_span_and_compile_first():
    spans, rec = _paired()
    records = _program()
    off = OFFSET
    lo, hi = off - 100, off + 1100
    # device ops on the trace clock, leaving gaps to attribute
    busy = [(0, 120), (140, 160), (240, 310), (330, 420), (440, 520),
            (560, 620), (680, 720), (790, 990)]
    ops = [(off + s, off + e, "op") for s, e in busy]
    att = ps.attribute(records, ops, spans, rec, lo, hi)
    assert att is not None and att.pairs == 16
    gaps_ns = {
        "none": 100 + 110,              # [-100, 0] and [990, 1100]
        "engine.run": 20,               # [310, 330]
        "prefill": 20,                  # [120, 140]
        "compile": 80 + 60,             # [160, 240] in the prefill,
                                        # [620, 680] in r1's token
        "decode.forward": 20,           # [420, 440]
        "decode.readback": 40 + 70,     # [520, 560], [720, 790]
    }
    assert att.by_span == pytest.approx(gaps_ns, abs=1e-6)
    assert att.compiling_ns == pytest.approx(140)
    assert att.readback_ns == pytest.approx(110)
    assert att.dispatch_ns == pytest.approx(20)        # decode.forward
    # which spans compiled, and the idle each compile left
    assert att.compiles_by_parent == {"prefill": 1, "decode.token": 1}
    assert att.compile_idle_by_parent == pytest.approx(
        {"prefill": 80, "decode.token": 60})
    # the attribution covers every idle nanosecond of the window
    assert sum(att.by_span.values()) == pytest.approx(att.idle_ns)
    assert att.share(att.compiling_ns) == pytest.approx(100 * 140 / 1200)


def test_a_compile_outranks_deeper_spans_it_overlaps():
    spans, rec = _paired()
    records = [
        ["engine.run", 0, 1000, None, None, {}],
        ["decode.step", 100, 900, 0, None, {"batch": 1}],
        ["decode.token", 100, 900, 1, "r0", {}],
        ["decode.forward", 200, 600, 2, None, {}],
        # stamped on another clock, a compile of the engine's can overlap
        # a deeper span it did not run under
        ["compile", 500, 700, 0, None, {"fun_name": "jit(h)"}],
    ]
    ops = [(OFFSET + s, OFFSET + e, "op")
           for s, e in ((0, 520), (580, 1000))]
    att = ps.attribute(records, ops, spans, rec, OFFSET, OFFSET + 1000)
    assert att.by_span == {"compile": 60}
    assert att.compiling_ns == 60 and att.dispatch_ns == 0


def test_programs_per_token_count_runs_inside_decode_steps():
    records = _program()
    off = 5e9
    modules = [(off + t, off + t + 5, "jit_x(1)")
               for t in (50, 410, 450, 505, 650, 790, 805, 900)]
    # 410, 450, 505, 650, 790 start inside the step [400, 800]
    assert ps.programs_per_token(records, modules, off, off, off + 1000) \
        == pytest.approx(5 / 2)
    assert ps.decode_programs(records, modules, off, off, off + 1000) \
        == (2, {"jit_x": 5})
    # a step outside the window is not counted
    assert ps.programs_per_token(records, modules, off, off + 500,
                                 off + 1000) is None


def test_readers_find_nothing_without_program_spans(monkeypatch):
    spans, rec = _paired()
    tr = DeviceTrace(ops={0: [(OFFSET, OFFSET + 10, "op")]},
                     modules={0: [(OFFSET, OFFSET + 10, "jit_x(1)")]},
                     spans=spans + [(OFFSET, OFFSET + 2e9, "bench:window")])
    ctx = Context(None, {}, [], rec, 2.0, 0, None, tr)
    monkeypatch.setattr(ps, "program_records", lambda: None)
    assert ps.idle_by_program_span(ctx) is None
    assert ps.decode_programs_per_token(ctx) is None
    ctx2 = Context(None, {}, [], rec, 2.0, 0, None, tr)
    monkeypatch.setattr(ps, "program_records", _program)
    att = ps.idle_by_program_span(ctx2)
    assert att is not None and att.pairs == 16
    assert ps.idle_by_program_span(ctx2) is att      # computed once a run
