"""Device time by the program's named scopes (``jax.named_scope``).

The program names the ops of a mechanism with a scope (``SCOPE_CATALOG``
in ``repro.obs.registry``: ``moe``, ``mla``).  The scope path reaches each
HLO instruction's ``op_name`` metadata, and the TPU's trace keeps it with
the op: each event of a chip's ``XLA Ops`` line points at an event
metadata record, whose stat :data:`SCOPE_STAT` holds that path
(``jit(_decode_program)/jit(decode_step)/while/body/closed_call/moe/
dot_general``).  ``jax.profiler.ProfileData`` gives an event's own stats
but not its metadata's, so this module reads the ``.xplane.pb`` itself,
with the installed protobuf runtime and a schema of the few fields of
``tsl/profiler/protobuf/xplane.proto`` that it needs.

``harness.trace.load`` keeps only each op's name and interval, and the
trace directory is removed before the readers run.  :func:`keep_scopes`
makes the load also keep, on the ``DeviceTrace`` it returns, the scope
path of every op of chip 0 (``scoped_ops``), on the same clock as
``ops``; a family whose cells read scopes calls it when it is loaded.
Where the program names no scope, or the trace holds no such stat, the
readers here find nothing to read.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from harness import program_spans, trace

SCOPE_STAT = "tf_op"          # the stat of an op's metadata with its path
OPS_LINE = "XLA Ops"


# ---------------------------------------------------------------------------
# the fields of tsl/profiler/protobuf/xplane.proto read here
# ---------------------------------------------------------------------------

_XPLANE = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, None), ("lines", 3, "XLine"),
               ("event_metadata", 4, "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [("key", 1, "int64"),
                                  ("value", 2, "XEventMetadata")],
    "XPlane.StatMetadataEntry": [("key", 1, "int64"),
                                 ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, None), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XEventMetadata": [("id", 1, "int64"), ("stats", 5, "XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, None)],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, None)],
}


@functools.lru_cache(maxsize=None)
def _xspace_class():
    """The ``XSpace`` message class over the fields above (unknown fields
    are skipped), parsed by the installed protobuf runtime."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    protos = {}
    for full in _XPLANE:
        outer, _, inner = full.partition(".")
        parent = protos[outer].nested_type.add() if inner \
            else fdp.message_type.add()
        parent.name = inner or outer
        if inner:
            parent.options.map_entry = True
        protos[full] = parent
    for full, fields in _XPLANE.items():
        for name, number, kind in fields:
            f = protos[full].field.add(name=name, number=number)
            repeated = kind not in (None, "int64") and not (
                full.endswith("Entry") and name == "value")
            f.label = F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL
            if kind is None:
                f.type = F.TYPE_STRING
            elif kind == "int64":
                f.type = F.TYPE_INT64
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = ".bench_xplane." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def device_op_scopes(plane) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, scope path)`` of every op of a chip's plane
    (its ``XLA Ops`` line), on the chip's clock."""
    stat = next((k for k, m in plane.stat_metadata.items()
                 if m.name == SCOPE_STAT), None)
    paths: Dict[int, str] = {}
    for k, meta in plane.event_metadata.items():
        for st in meta.stats:
            if st.metadata_id == stat:
                paths[k] = st.str_value
    out = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        t0 = line.timestamp_ns
        for ev in line.events:
            start = t0 + ev.offset_ps * 1e-3
            out.append((start, start + ev.duration_ps * 1e-3,
                        paths.get(ev.metadata_id, "")))
    return out


def read(log_dir: str) -> Dict[int, List[Tuple[float, float, str]]]:
    """Every chip's ops with their scope paths, from the newest
    ``.xplane.pb`` under ``log_dir`` (device clock)."""
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return {}
    space = _xspace_class()()
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m is not None:
            out[int(m.group(1))] = device_op_scopes(plane)
    return out


def keep_scopes():
    """Have ``harness.trace.load`` keep each op's scope path of chip 0 on
    the trace it returns (``scoped_ops``, on the host clock as ``ops``);
    the load it wraps stays reachable as ``trace.load.plain``."""
    if hasattr(trace.load, "plain"):
        return
    plain = trace.load

    def load(log_dir: str) -> trace.DeviceTrace:
        tr = plain(log_dir)
        shift = tr.offset_ns
        tr.scoped_ops = [(s + shift, e + shift, p)
                         for s, e, p in read(log_dir).get(0, [])]
        return tr

    load.plain = plain
    trace.load = load


# ---------------------------------------------------------------------------
# the readers' arithmetic
# ---------------------------------------------------------------------------


def in_scope(path: str, scope: str) -> bool:
    """Whether an op's path runs through the named scope."""
    return f"/{scope}/" in f"/{path}/"


def decode_ns_by_scope(scoped_ops, steps, scope: str) -> float:
    """Device ns of the ops under ``scope`` whose midpoint lies in one of
    the ``steps`` intervals ``(t0, t1)``, sorted by start."""
    starts = [s for s, _e in steps]
    total = 0.0
    for s, e, path in scoped_ops:
        if not in_scope(path, scope):
            continue
        mid = 0.5 * (s + e)
        j = bisect.bisect_right(starts, mid) - 1
        if j >= 0 and mid <= steps[j][1]:
            total += e - s
    return total


def decode_busy_ns(scoped_ops, steps) -> float:
    """Device ns during which some op ran inside one of the ``steps``
    (disjoint, sorted): the union of the ops clipped to each step, in one
    pass over the ops sorted by start."""
    merged = trace.union(scoped_ops)
    total, k = 0.0, 0
    for t0, t1 in steps:
        while k < len(merged) and merged[k][1] <= t0:
            k += 1
        j = k
        while j < len(merged) and merged[j][0] < t1:
            total += min(merged[j][1], t1) - max(merged[j][0], t0)
            j += 1
    return total


def device_ms_per_token(ctx, scope: str) -> Optional[float]:
    """Device ms of the ops under ``scope`` inside the window's decode
    steps, over the tokens those steps made; the share of the steps'
    device time they take goes to standard error."""
    scoped = getattr(ctx.trace, "scoped_ops", None) if ctx.trace else None
    att = program_spans.idle_by_program_span(ctx)
    if not scoped or att is None:
        return None
    lo, hi = ctx.trace_window()
    records = program_spans.program_records()
    off = att.offset_ns
    steps = sorted((t0 + off, t1 + off)
                   for name, t0, t1, _p, _r, _a in records
                   if name == program_spans.STEP and t1 is not None
                   and lo <= t0 + off and t1 + off <= hi)
    tokens, _runs = program_spans.decode_programs(
        records, ctx.trace.modules.get(0, []), off, lo, hi)
    ns = decode_ns_by_scope(scoped, steps, scope)
    if not tokens or not ns:
        return None
    busy = decode_busy_ns(scoped, steps)
    print(f"decode device time under {scope!r}: " + json.dumps(
        {"ms_per_token": ns * 1e-6 / tokens, "tokens": tokens,
         "share_of_decode_busy": ns / busy if busy else None}),
        file=sys.stderr, flush=True)
    return ns * 1e-6 / tokens
