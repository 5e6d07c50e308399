"""The program's own spans (``repro.obs.spans``) on the device trace's clock.

The program records its spans on ``time.perf_counter_ns``; the profiler
puts host events on its own clock.  The harness holds pairs on both: each
``bench:<kind>`` annotation in the trace and the probe's ``perf_counter``
record of the same call (``Record.ops`` by kind, ``Record.decodes``), in
order.  The offset between the clocks is the median over the pairs, the
residual the largest deviation from it.  With fewer than
:data:`MIN_PAIRS` pairs, or a residual over :data:`MAX_RESIDUAL_NS`, the
readers here find nothing to read.

Each idle stretch of the window on chip 0 goes to the innermost program
span open at its midpoint (the rule of ``trace.idle_by_span``), a
``compile`` span before any other; "none" where no program span was open.
The idle under ``engine.run`` itself is the engine's own scheduling time.

A program without the span recorder (``repro.obs.spans``) gives nothing.
"""
from __future__ import annotations

import bisect
import json
import statistics
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

MIN_PAIRS = 10
MAX_RESIDUAL_NS = 0.5e6
COMPILE = "compile"
STEP = "decode.step"
READBACK = "decode.readback"
OP_KINDS = ("compute", "load", "prefill")


def program_records() -> Optional[list]:
    """The program's span log, or None where the program has none."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans.log().records


def clock_pairs(trace_spans, rec) -> List[Tuple[float, float]]:
    """``(trace_ns, perf_ns)`` of every harness span matched, in order per
    kind, with the probe's record of the same call.  A kind whose counts
    differ on the two sides gives no pairs."""
    on_trace: Dict[str, List[float]] = {}
    for s, _e, name in sorted(trace_spans):
        if name.startswith("bench:"):
            on_trace.setdefault(name[len("bench:"):], []).append(s)
    probe: Dict[str, List[float]] = {k: [] for k in OP_KINDS}
    for op in rec.ops:
        probe.setdefault(op.kind, []).append(op.t0)
    probe["decode"] = [d.t0 for d in rec.decodes]
    out = []
    for kind, starts in probe.items():
        seen = on_trace.get(kind, [])
        if starts and len(seen) == len(starts):
            out += [(a, b * 1e9) for a, b in zip(seen, starts)]
    return out


def offset(pairs: Sequence[Tuple[float, float]]
           ) -> Optional[Tuple[float, float]]:
    """``(offset_ns, residual_ns)``: trace time = span time + offset."""
    if len(pairs) < MIN_PAIRS:
        return None
    d = [a - b for a, b in pairs]
    off = statistics.median(d)
    return off, max(abs(x - off) for x in d)


@dataclass
class Attribution:
    window_ns: float
    idle_ns: float
    by_span: Dict[str, float] = field(default_factory=dict)   # name -> ns
    compiling_ns: float = 0.0
    readback_ns: float = 0.0
    dispatch_ns: float = 0.0      # under decode.step, less the two above
    # compile spans of the window, and their idle, by the span they ran in
    compiles_by_parent: Dict[str, int] = field(default_factory=dict)
    compile_idle_by_parent: Dict[str, float] = field(default_factory=dict)
    offset_ns: float = 0.0
    residual_ns: float = 0.0
    pairs: int = 0

    def share(self, ns: float) -> float:
        return 100.0 * ns / self.window_ns

    def summary(self) -> dict:
        return {"pairs": self.pairs, "offset_ns": self.offset_ns,
                "residual_ms": self.residual_ns * 1e-6,
                "window_s": self.window_ns * 1e-9,
                "idle_s": self.idle_ns * 1e-9,
                "attributed_s": sum(self.by_span.values()) * 1e-9,
                "compiles_by_parent": dict(sorted(
                    self.compiles_by_parent.items(), key=lambda kv: -kv[1])),
                "compile_idle_by_parent_s": {
                    k: v * 1e-9 for k, v in sorted(
                        self.compile_idle_by_parent.items(),
                        key=lambda kv: -kv[1])},
                "idle_by_program_span_s": {
                    k: v * 1e-9 for k, v in sorted(
                        self.by_span.items(), key=lambda kv: -kv[1])}}


def _shifted(records, off: float, lo: float, hi: float):
    """Closed spans of non-zero length that meet [lo, hi], on the trace
    clock: ``(t0, t1, index)``, and each record's depth and whether it
    lies in a decode step."""
    depth: List[int] = []
    in_step: List[bool] = []
    out = []
    for i, (name, t0, t1, parent, _rid, _attrs) in enumerate(records):
        up = parent is not None and parent < i
        depth.append(depth[parent] + 1 if up else 0)
        in_step.append(name == STEP or (up and in_step[parent]))
        if t1 is not None and t1 > t0 and t1 + off > lo and t0 + off < hi:
            out.append((t0 + off, t1 + off, i))
    return out, depth, in_step


def _parent_name(records, i: int) -> str:
    p = records[i][3]
    return "none" if p is None else records[p][0]


def attribute(records, ops, trace_spans, rec, lo: float, hi: float
              ) -> Optional[Attribution]:
    """Idle device time of [lo, hi] by the program span open at each gap's
    midpoint (None when the clocks cannot be aligned)."""
    pairs = clock_pairs(trace_spans, rec)
    al = offset(pairs)
    if al is None or al[1] > MAX_RESIDUAL_NS:
        return None
    off, resid = al
    spans, depth, in_step = _shifted(records, off, lo, hi)
    spans.sort()
    gaps = trace.gaps(ops, lo, hi)
    att = Attribution(window_ns=hi - lo, idle_ns=sum(e - s for s, e in gaps),
                      offset_ns=off, residual_ns=resid, pairs=len(pairs))
    for t0, _t1, i in spans:
        if records[i][0] == COMPILE and t0 >= lo:
            up = _parent_name(records, i)
            att.compiles_by_parent[up] = att.compiles_by_parent.get(up, 0) + 1
    active: List[Tuple[float, float, int]] = []
    k = 0
    for s, e in gaps:                       # gaps come in time order
        mid = 0.5 * (s + e)
        while k < len(spans) and spans[k][0] <= mid:
            active.append(spans[k])
            k += 1
        active = [x for x in active if x[1] >= mid]
        name, i = "none", None
        if active:
            i = max(active, key=lambda x: (records[x[2]][0] == COMPILE,
                                           depth[x[2]], x[0]))[2]
            name = records[i][0]
        att.by_span[name] = att.by_span.get(name, 0.0) + (e - s)
        if name == COMPILE:
            att.compiling_ns += e - s
            up = _parent_name(records, i)
            att.compile_idle_by_parent[up] = \
                att.compile_idle_by_parent.get(up, 0.0) + (e - s)
        elif name == READBACK:
            att.readback_ns += e - s
        elif i is not None and in_step[i]:
            att.dispatch_ns += e - s
    return att


def idle_by_program_span(ctx) -> Optional[Attribution]:
    """The window's idle chip time by program span, once per run; the
    summary goes to standard error."""
    cached = ctx.__dict__.get("_program_idle", False)
    if cached is not False:
        return cached
    att = None
    records = program_records()
    if records and ctx.trace is not None and ctx.trace.ops.get(0):
        lo, hi = ctx.trace_window()
        att = attribute(records, ctx.trace.ops[0], ctx.trace.spans, ctx.rec,
                        lo, hi)
        if att is not None:
            print("program spans: " + json.dumps(att.summary()),
                  file=sys.stderr, flush=True)
    ctx.__dict__["_program_idle"] = att
    return att


def decode_programs(records, modules, off: float, lo: float, hi: float
                    ) -> Tuple[int, Dict[str, int]]:
    """Tokens the decode steps of [lo, hi] generated, and the program runs
    starting inside those steps, by program (``jit_<function>``)."""
    steps = sorted((t0 + off, t1 + off, attrs.get("batch", 0))
                   for name, t0, t1, _p, _r, attrs in records
                   if name == STEP and t1 is not None
                   and lo <= t0 + off and t1 + off <= hi)
    starts = [s for s, _e, _b in steps]
    runs: Dict[str, int] = {}
    for s, _e, name in modules:
        j = bisect.bisect_right(starts, s) - 1
        if j >= 0 and s <= steps[j][1]:
            prog = name.split("(")[0]
            runs[prog] = runs.get(prog, 0) + 1
    return sum(b for _s, _e, b in steps), runs


def programs_per_token(records, modules, off: float, lo: float, hi: float
                       ) -> Optional[float]:
    """Program runs starting inside the decode steps of [lo, hi], over the
    tokens those steps generated."""
    tokens, runs = decode_programs(records, modules, off, lo, hi)
    return sum(runs.values()) / tokens if tokens else None


def decode_programs_per_token(ctx) -> Optional[float]:
    """``programs_per_token`` of the window; the runs per token of each
    program go to standard error."""
    att = idle_by_program_span(ctx)
    if att is None or not ctx.trace.modules.get(0):
        return None
    lo, hi = ctx.trace_window()
    tokens, runs = decode_programs(program_records(), ctx.trace.modules[0],
                                   att.offset_ns, lo, hi)
    if not tokens:
        return None
    per_token = {k: v / tokens for k, v in sorted(
        runs.items(), key=lambda kv: -kv[1])}
    print("decode programs per token: " + json.dumps(
        {"tokens": tokens, "by_program": per_token}), file=sys.stderr,
        flush=True)
    return sum(runs.values()) / tokens
