"""Trip-count-corrected HLO cost walker (the §Roofline source)."""

import textwrap

import numpy as np
import pytest

from repro.launch import hlo_cost
from repro.launch.hlo_analysis import roofline_terms


def test_parse_and_trip_multiplication():
    hlo = textwrap.dedent("""\
    HloModule test

    %body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
      %p = (s32[], f32[64,64]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[64,64]{1,0} get-tuple-element(%p), index=1
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      %dot = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %t = (s32[], f32[64,64]) tuple(%ni, %dot)
    }

    %cond (p: (s32[], f32[64,64])) -> pred[] {
      %p = (s32[], f32[64,64]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(5)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    ENTRY %main (a: f32[64,64]) -> f32[64,64] {
      %a = f32[64,64]{1,0} parameter(0)
      %z = s32[] constant(0)
      %init = (s32[], f32[64,64]) tuple(%z, %a)
      %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
      ROOT %out = f32[64,64]{1,0} get-tuple-element(%w), index=1
    }
    """)
    cost = hlo_cost.analyze(hlo, 1)
    # 5 iterations × (2·64·64·64 dot flops + 64·64... small adds)
    assert cost.flops == pytest.approx(5 * 2 * 64 * 64 * 64, rel=0.01)


def test_collective_wire_model():
    hlo = textwrap.dedent("""\
    HloModule coll

    ENTRY %main (a: f32[1024]) -> f32[1024] {
      %a = f32[1024]{0} parameter(0)
      %ar = f32[1024]{0} all-reduce(%a), replica_groups=[2,4]<=[8], to_apply=%add
      %ag = f32[4096]{0} all-gather(%ar), replica_groups=[2,4]<=[8], dimensions={0}
      ROOT %rs = f32[1024]{0} reduce-scatter(%ag), replica_groups=[2,4]<=[8], dimensions={0}
    }
    """)
    cost = hlo_cost.analyze(hlo, 8)
    b = 1024 * 4
    # AR: 2·b·3/4 ; AG: out 4b → 4b·3/4 = 3b ; RS: out b → b·(n-1) = 3b
    assert cost.coll_bytes["all-reduce"] == pytest.approx(2 * b * 3 / 4)
    assert cost.coll_bytes["all-gather"] == pytest.approx(3 * b)
    assert cost.coll_bytes["reduce-scatter"] == pytest.approx(3 * b)
    assert cost.coll_ops == {"all-reduce": 1, "all-gather": 1,
                             "reduce-scatter": 1}


def test_real_scan_flops_match_unrolled():
    """Walker(scan-HLO) ≈ cost_analysis(unrolled-HLO) on the same program."""
    import jax
    import jax.numpy as jnp

    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(ws, x):
        return jnp.sum(jax.lax.scan(body, x, ws)[0])

    def unrolled(ws, x):
        for i in range(8):
            x, _ = body(x, ws[i])
        return jnp.sum(x)

    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cs = jax.jit(scanned).lower(ws, x).compile()
    cu = jax.jit(unrolled).lower(ws, x).compile()
    walker = hlo_cost.analyze(cs.as_text(), 1).flops
    xla_unrolled = cu.cost_analysis()["flops"]
    assert walker == pytest.approx(xla_unrolled, rel=0.05)


def test_roofline_terms_and_dominance():
    rl = roofline_terms({"flops": 197e12, "bytes accessed": 819e9 * 2},
                        wire_bytes=0.0, model_flops_per_device=197e12 / 2)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(2.0)
    assert rl.dominant == "memory"
    assert rl.useful_flops_ratio == pytest.approx(0.5)
    assert rl.roofline_fraction == pytest.approx(0.25)
