"""Device time by named scope (``harness.op_scopes``).

``data/scopes`` is a trace recorded on a TPU v5e of a jitted scan whose
body runs one matmul under ``jax.named_scope("moe")`` and one under
``"mla"``, three times; XLA fused the two, so every fused op carries
the ``mla`` path of its root.
"""
import os

from harness import op_scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_ops_carry_their_scope_path():
    ops = op_scopes.read(os.path.join(DATA, "scopes"))
    assert list(ops) == [0]
    paths = [p for _s, _e, p in ops[0]]
    assert "jit(f)/outer/while/body/closed_call/mla/dot_general:" in paths
    mla = [(s, e) for s, e, p in ops[0] if op_scopes.in_scope(p, "mla")]
    assert len(mla) == 8 and all(e > s for s, e in mla)


def test_the_load_keeps_scopes_and_moves_nothing_else():
    op_scopes.keep_scopes()
    op_scopes.keep_scopes()               # once is enough
    patched = trace.load
    plain = patched.plain
    assert not hasattr(plain, "plain")
    for name in ("scopes", "tpu"):
        got = patched(os.path.join(DATA, name))
        want = plain(os.path.join(DATA, name))
        assert (got.ops, got.modules, got.spans, got.offset_ns) == \
            (want.ops, want.modules, want.spans, want.offset_ns)
    got = patched(os.path.join(DATA, "scopes"))
    raw = op_scopes.read(os.path.join(DATA, "scopes"))[0]
    assert got.scoped_ops == [(s + got.offset_ns, e + got.offset_ns, p)
                              for s, e, p in raw]
    # the trace's own ops, one for one, on its clock (which keeps whole ns)
    assert len(got.scoped_ops) == len(got.ops[0])
    for (s, e, _p), (s0, e0, _n) in zip(got.scoped_ops, got.ops[0]):
        assert abs(s - s0) <= 2 and abs(e - e0) <= 2


def test_scope_time_by_decode_step():
    ops = [(0, 10, "jit(p)/while/body/moe/dot:"), (10, 14, "jit(p)/mla/x:"),
           (30, 40, "jit(p)/moe/y:"), (50, 60, "jit(p)/moe/z:"),
           (12, 13, "jit(p)/moemoe/w:")]
    steps = [(0, 20), (28, 45)]
    assert op_scopes.decode_ns_by_scope(ops, steps, "moe") == 20
    assert op_scopes.decode_ns_by_scope(ops, steps, "mla") == 4
    assert op_scopes.decode_ns_by_scope(ops, [], "moe") == 0
    # the steps' busy time: overlapping ops counted once, clipped to steps
    assert op_scopes.decode_busy_ns(ops, steps) == 14 + 10
    assert op_scopes.decode_busy_ns(ops, steps) == sum(
        trace.busy_ns(ops, t0, t1) for t0, t1 in steps)
    assert op_scopes.in_scope("a/moe/b:", "moe")
    assert not op_scopes.in_scope("a/moe_x/b:", "moe")
