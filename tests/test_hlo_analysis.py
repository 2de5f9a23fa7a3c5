"""HLO roofline-extraction parser tests (the §Roofline machinery)."""
import textwrap

import pytest

from repro.launch.hlo_analysis import (_shape_bytes, analyze,
                                       count_collectives, parse_hlo)

SAMPLE = textwrap.dedent("""\
    HloModule jit_f

    %body (param: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
      %param = (s32[], f32[64,64]{1,0}) parameter(0)
      %gte0 = s32[] get-tuple-element(%param), index=0
      %gte1 = f32[64,64]{1,0} get-tuple-element(%param), index=1
      %c1 = s32[] constant(1)
      %add = s32[] add(%gte0, %c1)
      %ag = f32[64,128]{1,0} all-gather(%gte1), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
      %dot = f32[64,64]{1,0} dot(%gte1, %gte1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[64,64]{1,0} all-reduce(%dot), channel_id=2, replica_groups=[2,4]<=[8]
      ROOT %tuple = (s32[], f32[64,64]{1,0}) tuple(%add, %ar)
    }

    %cond (param.1: (s32[], f32[64,64])) -> pred[] {
      %param.1 = (s32[], f32[64,64]{1,0}) parameter(0)
      %gte = s32[] get-tuple-element(%param.1), index=0
      %c10 = s32[] constant(10)
      ROOT %lt = pred[] compare(%gte, %c10), direction=LT
    }

    ENTRY %main (p0: f32[64,64]) -> f32[64,64] {
      %p0 = f32[64,64]{1,0} parameter(0)
      %c0 = s32[] constant(0)
      %t = (s32[], f32[64,64]{1,0}) tuple(%c0, %p0)
      %w = (s32[], f32[64,64]{1,0}) while(%t), condition=%cond, body=%body
      ROOT %out = f32[64,64]{1,0} get-tuple-element(%w), index=1
    }
    """)


def test_shape_bytes():
    assert _shape_bytes("f32[64,64]{1,0}") == 64 * 64 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(s32[], f32[4])") == 4 + 16
    assert _shape_bytes("pred[]") == 1


def test_parse_computations():
    comps = parse_hlo(SAMPLE)
    assert set(comps) >= {"body", "cond", "main", "__entry__"}
    assert comps["__entry__"].name == "main"
    ops = [i.opcode for i in comps["body"].instrs]
    assert "dot" in ops and "all-gather" in ops and "all-reduce" in ops


def test_loop_multiplied_flops_and_collectives():
    r = analyze(SAMPLE, default_group=8)
    # dot: 2*64*64*64 flops, x10 loop trips
    assert r["flops"] == pytest.approx(10 * 2 * 64 ** 3)
    # all-gather result 64x128 f32 = 32768B, ring (4-1)/4, x10
    ag = 10 * 32768 * 3 / 4
    # all-reduce 64x64 f32 = 16384B, ring 2*(4-1)/4, x10
    ar = 10 * 16384 * 2 * 3 / 4
    assert r["coll/all-gather"] == pytest.approx(ag)
    assert r["coll/all-reduce"] == pytest.approx(ar)
    assert r["collective_bytes"] == pytest.approx(ag + ar)


def test_traffic_excludes_aliasing_ops():
    r = analyze(SAMPLE, default_group=8)
    # per iteration: add(4) + ag(32768) + dot(16384) + ar(16384); the
    # parameter/tuple/gte/while ops contribute nothing.
    per_iter = 4 + 32768 + 16384 + 16384
    assert r["traffic_bytes"] == pytest.approx(10 * per_iter)


def test_count_collectives_loop_multiplied():
    c = count_collectives(SAMPLE)
    # one all-gather + one all-reduce per body iteration, x10 trips
    assert c["all-gather"] == 10
    assert c["all-reduce"] == 10
    assert c["all-to-all"] == 0 and c["collective-permute"] == 0
    assert c["total"] == 20


def test_count_collectives_async_pairs_count_once():
    text = SAMPLE.replace(
        "%ag = f32[64,128]{1,0} all-gather(%gte1), channel_id=1, "
        "replica_groups=[2,4]<=[8], dimensions={1}",
        "%ags = f32[64,128]{1,0} all-gather-start(%gte1), channel_id=1, "
        "replica_groups=[2,4]<=[8], dimensions={1}\n"
        "      %ag = f32[64,128]{1,0} all-gather-done(%ags)")
    c = count_collectives(text)
    assert c["all-gather"] == 10  # -start counted, -done skipped


def test_count_collectives_clean_module():
    c = count_collectives("HloModule m\n\nENTRY %main (p: f32[4]) -> "
                          "f32[4] {\n  ROOT %p = f32[4] parameter(0)\n}\n")
    assert c["total"] == 0


def test_analyze_real_lowered_module():
    """End-to-end: the parser agrees with hand-counted flops of a real
    scanned matmul (cost_analysis undercounts by the trip count)."""
    import jax
    import jax.numpy as jnp

    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    w = jax.ShapeDtypeStruct((7, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    compiled = jax.jit(f).lower(w, x).compile()
    r = analyze(compiled.as_text())
    want = 7 * 2 * 8 * 32 * 32
    assert r["flops"] == pytest.approx(want, rel=0.01)
    xla = compiled.cost_analysis()["flops"]
    assert xla < r["flops"]  # the undercount this parser exists to fix
