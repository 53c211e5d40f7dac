"""HLO cost parser: validated against XLA cost_analysis ground truth."""

import pytest

from repro.roofline.hlo_parse import parse_hlo


def test_parser_on_synthetic_hlo():
    text = """
HloModule test

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant({...})
  %dot.1 = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16] all-reduce(%dot.1), replica_groups={}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ip, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%z, %a)
  %w2 = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[8,16] get-tuple-element(%w2), index=1
}
"""
    cost = parse_hlo(text)
    # dot: 2*8*16*16 = 4096 flops x 5 trips
    assert cost.flops == 4096 * 5
    # all-reduce: 8*16*4 bytes x 5 trips
    assert cost.collective_bytes == 512 * 5
    assert cost.while_trip_counts == [5]
    assert cost.collective_by_type == {"all-reduce": 512 * 5}


def test_parser_vs_cost_analysis_unrolled(subproc):
    """On an UNROLLED program (no while), parsed flops ~== XLA's."""
    out = subproc("""
        import jax, jax.numpy as jnp
        from repro.roofline.hlo_parse import parse_hlo
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        f = lambda x, y: (x @ y).sum()
        c = jax.jit(f).lower(a, b).compile()
        got = parse_hlo(c.as_text()).flops
        want = c.cost_analysis()["flops"]
        assert abs(got - want) / want < 0.05, (got, want)
        print("OK")
    """, devices=1)
    assert "OK" in out


def test_parser_scan_trip_multiplier(subproc):
    """With lax.scan, XLA undercounts by the trip count; the parser must
    recover the x L factor."""
    out = subproc("""
        import jax, jax.numpy as jnp
        from repro.roofline.hlo_parse import parse_hlo
        L = 7
        def f(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), ()
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()
        ws = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        c = jax.jit(f).lower(ws, x).compile()
        cost = parse_hlo(c.as_text())
        assert L in cost.while_trip_counts, cost.while_trip_counts
        per_layer = 2 * 8 * 64 * 64
        assert cost.flops >= per_layer * L * 0.9, (cost.flops, per_layer * L)
        print("OK")
    """, devices=1)
    assert "OK" in out


def test_parser_finds_collectives_in_sharded_program(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline.hlo_parse import parse_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
        f = lambda x, w: (x @ w).sum()
        c = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P("data", None)),
                NamedSharding(mesh, P("model", None))),
            out_shardings=NamedSharding(mesh, P())).lower(x, w).compile()
        cost = parse_hlo(c.as_text())
        assert cost.collective_bytes > 0
        assert cost.collective_count > 0
        print("OK")
    """, devices=8)
    assert "OK" in out
