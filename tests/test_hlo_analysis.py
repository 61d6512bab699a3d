"""HLO call-graph analyzer unit tests (pure text fixtures + one real
lowering on a 1x1 mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as HA
from repro.launch.scan_registry import clear_registry, get_registry, \
    tagged_scan

FIXTURE = """
HloModule test

%body.1 (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant({...})
  %d = f32[8,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%d), to_apply=%add.1, metadata={op_name="x"}
  ROOT %t = (s32[], f32[8,128]{1,0}) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (arg: f32[8,128]) -> f32[8,128] {
  %arg = f32[8,128]{1,0} parameter(0)
  %c = s32[] constant(0)
  %t0 = (s32[], f32[8,128]{1,0}) tuple(%c, %arg)
  %wh = (s32[], f32[8,128]{1,0}) while(%t0), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/myscan_tag/while"}
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_fixture_flops_and_collectives():
    res = HA.analyze(FIXTURE, {"myscan_tag": 5})
    # dot: 2*8*128*128 flops, x5 iterations
    assert res["dot_flops"] == pytest.approx(2 * 8 * 128 * 128 * 5)
    # all-reduce: 8*128*4 bytes output, wire = 2x, x5
    assert res["collective_wire_bytes"]["all-reduce"] == \
        pytest.approx(2 * 8 * 128 * 4 * 5)
    assert res["unknown_whiles"] == []


def test_fixture_unknown_while_counts_once():
    res = HA.analyze(FIXTURE, {"not_matching": 9})
    assert res["dot_flops"] == pytest.approx(2 * 8 * 128 * 128)
    assert len(res["unknown_whiles"]) == 1


def test_shape_bytes_tuple_with_index_comments():
    txt = "(s32[], f32[32,64]{1,0}, /*index=5*/bf16[7,2]{1,0})"
    assert HA.shape_bytes(txt) == 4 + 32 * 64 * 4 + 7 * 2 * 2


def test_real_lowering_matches_hand_count(key):
    """End-to-end: tagged scan over 6 matmul layers, 1-device mesh."""
    clear_registry()

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        out, _ = tagged_scan("tagscan_layers_fwd", body, x, w, length=6)
        return out.sum()

    fn = jax.jit(jax.grad(f, argnums=1))
    xs = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    compiled = fn.lower(xs, ws).compile()
    res = HA.analyze(compiled.as_text(), get_registry())
    # fwd dot + 2 bwd dots per layer, 6 layers
    expected = 3 * 2 * 16 * 64 * 64 * 6
    assert res["dot_flops"] == pytest.approx(expected, rel=0.35)
    assert res["dot_flops"] > compiled.cost_analysis().get("flops", 0.0)
    assert res["unknown_whiles"] == []


def test_scan_registry_length_qualified():
    """Same tag at two lengths registers two distinct qualified entries
    (no cross-trace corruption)."""
    clear_registry()

    def body(c, x):
        return c + x, None

    tagged_scan("tagscan_test_a", body, jnp.zeros(()), jnp.ones(4),
                length=4)
    tagged_scan("tagscan_test_a", body, jnp.zeros(()), jnp.ones(5),
                length=5)
    reg = get_registry()
    assert reg["tagscan_test_a_L4"] == 4
    assert reg["tagscan_test_a_L5"] == 5
    clear_registry()
