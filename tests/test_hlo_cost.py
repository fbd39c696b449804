"""The loop-aware HLO cost model is the roofline measurement instrument —
validate it against XLA's own cost_analysis where XLA is correct (no loops)
and against analytical counts where XLA is wrong (scan bodies)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matches_xla_on_straightline():
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    c = _compile(f, a, b)
    got = hlo_cost.analyze(c.as_text(), 1)
    xla = c.cost_analysis()
    # dot flops dominate; ours adds elementwise tanh
    assert abs(got.flops - xla["flops"]) / xla["flops"] < 0.05
    assert abs(got.bytes - xla["bytes accessed"]) / xla["bytes accessed"] < 0.2


def test_scan_multiplied_by_trip_count():
    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    T = 12
    ws = jax.ShapeDtypeStruct((T, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    c = _compile(f, ws, x)
    got = hlo_cost.analyze(c.as_text(), 1)
    dot_flops = 2 * 8 * 64 * 64
    assert got.flops == pytest.approx(T * dot_flops, rel=0.05)
    # XLA undercounts by the trip count (the motivating bug)
    assert c.cost_analysis()["flops"] == \
        pytest.approx(dot_flops, rel=0.05)


def test_nested_scan():
    def f(ws, x):
        def outer(h, w):
            def inner(g, _):
                return jnp.tanh(g @ w), None
            g, _ = jax.lax.scan(inner, h, None, length=3)
            return g, None
        h, _ = jax.lax.scan(outer, x, ws)
        return h

    ws = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    c = _compile(f, ws, x)
    got = hlo_cost.analyze(c.as_text(), 1)
    assert got.flops == pytest.approx(4 * 3 * 2 * 8 * 32 * 32, rel=0.1)


def test_collectives_counted_with_group_size():
    import os
    import re
    # parse a hand-written HLO snippet (device-count independent)
    hlo = """
HloModule test
ENTRY %main (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  ROOT %ar = f32[64,64]{1,0} all-reduce(%p), replica_groups=[2,4]<=[8], to_apply=%add
}
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}
"""
    got = hlo_cost.analyze(hlo, 8)
    bytes_full = 64 * 64 * 4
    want = 2 * bytes_full * (4 - 1) / 4          # ring, group size 4
    assert got.collective_wire_bytes == pytest.approx(want)
    assert got.collective_counts["all-reduce"] == 1


def test_shape_parser_tuples_and_layouts():
    s, pos = hlo_cost._parse_shape("(f32[2,3]{1,0}, (bf16[4], pred[]))")
    assert s.bytes == 2 * 3 * 4 + 4 * 2 + 1
