"""Fixture tests for the static auditor (``repro.analysis``).

Each shipped rule ID is demonstrated by a deliberately broken fixture
that must trip exactly that rule, the allowlist round-trips (justified
comments suppress, silent/mismatched ones don't), and the repo itself
audits clean — the same invariant CI enforces with
``python -m repro.analysis --all``.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis.findings import Allowlist, Finding, RULES
from repro.analysis.ast_rules import audit_ast
from repro.analysis.pallas_lint import (PallasCapture, SpecInfo,
                                        capture_pallas_calls, check_capture,
                                        check_seed_uniqueness)

REPO = Path(__file__).resolve().parent.parent


def _rules_hit(findings, rule):
    return [f for f in findings if f.rule == rule]


# --------------------------------------------------------------------------
# Layer 2 fixtures — Pallas grid safety
# --------------------------------------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _capture_1d(out_index_map, grid=4, blocks=4, block=8):
    """Capture a 1-D pallas_call whose out spec is under test."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(grid,),
            in_specs=[pl.BlockSpec((block,), lambda i: (i % blocks,))],
            out_specs=pl.BlockSpec((block,), out_index_map),
            out_shape=jax.ShapeDtypeStruct((blocks * block,), jnp.float32),
            interpret=True,
        )(x)

    x = jax.ShapeDtypeStruct((blocks * block,), jnp.float32)
    caps = capture_pallas_calls(fn, x, entry="fixture")
    assert len(caps) == 1
    return caps[0]


def test_ra201_overlapping_out_spec_write_race():
    # grid step 0 and 2 both write output block 0 (steps 1 and 3 write
    # block 1): a non-consecutive revisit, the classic overlapping-out
    # -spec race.
    cap = _capture_1d(lambda i: (i % 2,), grid=4, blocks=2)
    hits = _rules_hit(check_capture(cap), "RA201")
    assert hits, "overlapping out spec must trip RA201"
    assert "non-consecutive" in hits[0].message


def test_ra201_incomplete_coverage():
    # every grid step writes block 0; blocks 1..3 are never written.
    cap = _capture_1d(lambda i: (0,), grid=4, blocks=4)
    hits = _rules_hit(check_capture(cap), "RA201")
    assert hits and "never written" in hits[0].message


def test_ra201_legal_accumulator_revisits_pass():
    # consecutive revisits (block i//2) are the legal accumulator
    # pattern: complete and race-free.
    cap = _capture_1d(lambda i: (i // 2,), grid=4, blocks=2)
    assert check_capture(cap) == []


def test_ra202_out_of_bounds_block():
    cap = _capture_1d(lambda i: (i + 1,), grid=4, blocks=4)
    hits = _rules_hit(check_capture(cap), "RA202")
    assert hits and "outside block grid" in hits[0].message


def test_ra203_shape_not_divisible_by_block():
    # Hand-built capture: pallas itself may mask a ragged tail, but the
    # repo wrappers promise pre-padded operands — the auditor enforces it.
    cap = PallasCapture(
        entry="fixture", kernel_name="k", grid=(3,),
        specs=[SpecInfo(block_shape=(4,), index_map=lambda i: (i,),
                        shape=(10,), role="out[0]")])
    hits = _rules_hit(check_capture(cap), "RA203")
    assert hits and "not divisible" in hits[0].message


def test_ra204_duplicate_seed_base():
    dup = [("blocks/0/attn", (2, 2, 2), 0x1234),
           ("blocks/1/mlp", (2, 2, 2), 0x1234)]
    hits = _rules_hit(check_seed_uniqueness(dup), "RA204")
    assert hits and "same base seed" in hits[0].message


def test_ra204_unique_seed_grid_passes():
    ok = [("blocks/0/attn", (4, 8, 8), 0x1234),
          ("blocks/1/mlp", (4, 8, 8), 0x5678)]
    assert check_seed_uniqueness(ok) == []


# --------------------------------------------------------------------------
# Layer 1 fixtures — jaxpr contracts
# --------------------------------------------------------------------------

def test_ra101_f64_leak():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_lint import check_no_f64

    jax.config.update("jax_enable_x64", True)
    try:
        closed = jax.make_jaxpr(
            lambda x: jnp.sum(x.astype(jnp.float64)))(
                jax.ShapeDtypeStruct((4,), jnp.float32))
    finally:
        jax.config.update("jax_enable_x64", False)
    hits = _rules_hit(check_no_f64(closed, "fixture"), "RA101")
    assert hits and "float64" in hits[0].message

    clean = jax.make_jaxpr(lambda x: x * 2)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert check_no_f64(clean, "fixture") == []


def test_ra102_tape_in_grad_tree():
    from repro.analysis.jaxpr_lint import check_tape_containment

    # A conductance leaf sharing the tape site in the differentiated
    # tree: the symbolic-zero hoist failed.
    diff = {"blocks": {"0": {"wq": {"x_tape": 1, "d_tape": 2, "s_tape": 6,
                                    "g": 3}}}}
    frozen = {"blocks": {"0": {"wq": {"g": 3, "ref": 4, "w_scale": 5}}}}
    hits = _rules_hit(check_tape_containment(diff, frozen, "fx"), "RA102")
    assert hits and "['g']" in hits[0].message

    # A frozen container missing its hoisted leaves is the dual failure.
    hits = _rules_hit(check_tape_containment(
        {"wq": {"x_tape": 1, "d_tape": 2, "s_tape": 6}},
        {"wq": {"g": 3}}, "fx"), "RA102")
    assert hits and "missing" in hits[0].message

    # Codes without their scale slot are no write operands.
    hits = _rules_hit(check_tape_containment(
        {"wq": {"x_tape": 1, "d_tape": 2}},
        {"wq": {"g": 3, "ref": 4, "w_scale": 5}}, "fx"), "RA102")
    assert hits and "s_tape" in hits[0].message

    # Code tapes in another dtype than the one that holds the codes.
    import jax.numpy as jnp
    f32 = jnp.zeros((2, 3), jnp.float32)
    hits = _rules_hit(check_tape_containment(
        {"wq": {"x_tape": f32, "d_tape": f32, "s_tape": 6}},
        {"wq": {"g": 3, "ref": 4, "w_scale": 5}}, "fx",
        code_dtype=jnp.bfloat16), "RA102")
    assert hits and "bfloat16" in hits[0].message

    # The shipped shape passes.
    bf16 = f32.astype(jnp.bfloat16)
    assert check_tape_containment(
        {"wq": {"x_tape": bf16, "d_tape": bf16, "s_tape": 6}},
        {"wq": {"g": 3, "ref": 4, "w_scale": 5}}, "fx",
        code_dtype=jnp.bfloat16) == []


def test_ra103_collective_in_shard_map_body():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.analysis.jaxpr_lint import check_collectives
    from repro.kernels.xbar_update import _wrap_shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    fn = _wrap_shard_map(lambda x: jax.lax.psum(x, "model"), mesh,
                         (P("model"),), P())
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4,), jnp.float32))
    hits = _rules_hit(
        check_collectives(closed, "fx", whitelist=set()), "RA103")
    assert hits and "psum" in hits[0].message
    # the same trace passes once psum is whitelisted
    assert check_collectives(closed, "fx", whitelist={"psum"}) == []


def test_ra103_default_whitelist_flags_conductance_gather():
    """The known-bad shape the rework exists for: a full-conductance
    ``all_gather`` inside an exact-mode shard_map body.  The default
    whitelist is empty now, so the gather is a finding unless its source
    line carries an inline justification — which this fixture's does
    not."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.analysis.jaxpr_lint import (EXACT_MODE_WHITELIST,
                                           check_collectives)
    from repro.kernels.xbar_update import _wrap_shard_map

    assert EXACT_MODE_WHITELIST == set()
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))

    def gather_then_replay(g_block):  # the legacy read's first move
        return jax.lax.all_gather(g_block, "model", axis=0, tiled=True)

    fn = _wrap_shard_map(gather_then_replay, mesh, (P("model"),), P())
    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32))
    hits = _rules_hit(check_collectives(closed, "fx"), "RA103")
    assert hits and "all_gather" in hits[0].message
    # the finding anchors to THIS file (no justification here), so the
    # repo allowlist must not suppress it
    active, suppressed = Allowlist(root=str(REPO)).split(hits)
    assert active and not suppressed


def test_ra107_parameter_sized_collective_in_compiled_module():
    from repro.analysis.jaxpr_lint import check_parameter_sized_collectives

    # 64x256 f32 operand = 65536 bytes: a conductance-block-scale gather.
    bad = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p: f32[64,256]) -> f32[128,256] {
          %p = f32[64,256]{1,0} parameter(0)
          ROOT %ag = f32[128,256]{1,0} all-gather(%p), channel_id=1, replica_groups=[2,1]<=[2], dimensions={0}
        }
        """)
    hits = _rules_hit(
        check_parameter_sized_collectives(bad, 65536, "fx"), "RA107")
    assert hits and "parameter-sized" in hits[0].message
    # an activation-sized combine (4x256 f32 = 4096 B) stays clean
    ok = bad.replace("f32[64,256]", "f32[4,256]") \
            .replace("f32[128,256]", "f32[8,256]")
    assert check_parameter_sized_collectives(ok, 65536, "fx") == []


def test_ra104_missing_donation():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_lint import check_donation

    x = jnp.zeros((8,), jnp.float32)
    plain = jax.jit(lambda x: x + 1).lower(x).as_text()
    hits = _rules_hit(check_donation(plain, "fx"), "RA104")
    assert hits and "no donated buffer" in hits[0].message

    donated = jax.jit(lambda x: x + 1,
                      donate_argnums=(0,)).lower(x).as_text()
    assert check_donation(donated, "fx") == []


def test_ra105_budgets():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_lint import check_clip_round_budget

    # pjit-wrapped clip: the de-fused ADC-chain shape the rule exists for.
    closed = jax.make_jaxpr(
        lambda x: jax.jit(jnp.clip)(x, -1.0, 1.0))(
            jax.ShapeDtypeStruct((4,), jnp.float32))
    hits = _rules_hit(check_clip_round_budget(closed, "fx"), "RA105")
    assert hits and "pjit-wrapped" in hits[0].message

    # equation budget
    small = jax.make_jaxpr(lambda x: x * 2 + 1)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    hits = _rules_hit(
        check_clip_round_budget(small, "fx", max_eqns=1), "RA105")
    assert hits and "budget" in hits[0].message
    assert check_clip_round_budget(small, "fx") == []


def test_ra106_order_sensitive_collective_in_compiled_module():
    from repro.analysis.jaxpr_lint import check_compiled_collectives

    bad = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p: f32[8,8]) -> f32[8,8] {
          %p = f32[8,8]{1,0} parameter(0)
          %rs = f32[4,8]{1,0} reduce-scatter(%p), channel_id=1, replica_groups=[2,1]<=[2], dimensions={0}, to_apply=%add
          ROOT %ag = f32[8,8]{1,0} all-gather(%rs), channel_id=2, replica_groups=[2,1]<=[2], dimensions={0}
        }
        """)
    hits = _rules_hit(check_compiled_collectives(bad, "fx"), "RA106")
    assert hits and "reduce-scatter" in hits[0].message

    ok = bad.replace("reduce-scatter", "all-reduce")
    assert check_compiled_collectives(ok, "fx") == []


# --------------------------------------------------------------------------
# Layer 3 fixtures — AST rules
# --------------------------------------------------------------------------

def _audit_source(tmp_path, source, rel="src/repro/train/bad.py"):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return audit_ast(root=str(tmp_path), files=[str(path)])


def test_ra301_config_mutation(tmp_path):
    findings = _audit_source(tmp_path, """
        import jax
        jax.config.update("jax_enable_x64", True)
        jax.config.jax_default_matmul_precision = "highest"
    """, rel="src/repro/core/bad.py")
    hits = _rules_hit(findings, "RA301")
    assert len(hits) == 2  # call form + attribute form


def test_ra302_host_rng_in_kernel(tmp_path):
    findings = _audit_source(tmp_path, """
        import jax

        def _update_kernel(g_ref, o_ref):
            noise = jax.random.normal(jax.random.PRNGKey(0), (8,))
            o_ref[...] = g_ref[...] + noise

        def host_fn(x):   # outside a kernel body: fine
            return jax.random.normal(jax.random.PRNGKey(0), x.shape)
    """, rel="src/repro/kernels/bad.py")
    hits = _rules_hit(findings, "RA302")
    # PRNGKey + normal inside the kernel body only
    assert len(hits) == 2
    assert all(h.line <= 6 for h in hits)


def test_ra303_container_op_in_loop(tmp_path):
    findings = _audit_source(tmp_path, """
        def forward(params, x, cfg):
            for layer in params:
                x = vmm(x, layer["g"], layer["ref"], layer["ws"], cfg)
            return x
    """, rel="src/repro/models/bad.py")
    hits = _rules_hit(findings, "RA303")
    assert hits and "vmm" in hits[0].message


def test_ra304_jit_without_donation(tmp_path):
    findings = _audit_source(tmp_path, """
        import jax

        step = jax.jit(lambda s, b: s)

        @jax.jit
        def decorated(s):
            return s

        good = jax.jit(lambda s, b: s, donate_argnums=(0,))
    """)
    hits = _rules_hit(findings, "RA304")
    assert len(hits) == 2  # call form + bare decorator; donated one passes


def test_ra304_only_in_step_owning_dirs(tmp_path):
    findings = _audit_source(tmp_path, """
        import jax
        probe = jax.jit(lambda x: x)
    """, rel="src/repro/core/fine.py")
    assert _rules_hit(findings, "RA304") == []


# --------------------------------------------------------------------------
# Allowlist round-trip
# --------------------------------------------------------------------------

def test_allowlist_round_trip(tmp_path):
    src = """
        def forward(params, x, cfg):
            for layer in params:
                # audit: allow RA303 -- fixture: bounded 2-cell loop
                x = vmm(x, layer, cfg)
            return x
    """
    findings = _audit_source(tmp_path, src, rel="src/repro/models/ok.py")
    active, suppressed = Allowlist(root=str(tmp_path)).split(findings)
    assert _rules_hit(active, "RA303") == []
    assert any(f.rule == "RA303" and "bounded 2-cell" in why
               for f, why in suppressed)


def test_allowlist_rejects_silent_and_mismatched(tmp_path):
    src = """
        def forward(params, x, cfg):
            for layer in params:
                # audit: allow RA303
                x = vmm(x, layer, cfg)
            y = mvm(x, params[0], cfg)  # audit: allow RA304 -- wrong rule
            return y
    """
    # mvm sits in a loop too? no — it's outside the for body, but keep
    # the loop finding on vmm: silent comment must NOT suppress it, and
    # the wrong-rule comment must not suppress anything either.
    findings = _audit_source(tmp_path, src, rel="src/repro/models/bad.py")
    active, suppressed = Allowlist(root=str(tmp_path)).split(findings)
    assert _rules_hit(active, "RA303"), \
        "justification-free allowlist comment must not suppress"
    assert suppressed == []


def test_unanchored_findings_are_never_suppressible():
    f = Finding("RA101", "f64 deep inside jax", entry="train_step")
    active, suppressed = Allowlist().split([f])
    assert active == [f] and suppressed == []


# --------------------------------------------------------------------------
# Catalog + CLI + repo-clean
# --------------------------------------------------------------------------

def test_rule_catalog_is_stable():
    assert set(RULES) >= {
        "RA101", "RA102", "RA103", "RA104", "RA105", "RA106", "RA107",
        "RA201", "RA202", "RA203", "RA204",
        "RA301", "RA302", "RA303", "RA304",
    }


def test_cli_list_rules(capsys):
    from repro.analysis.cli import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "RA201" in out and "RA304" in out


def test_repo_ast_layer_is_clean():
    active, _ = Allowlist().split(audit_ast())
    assert active == [], "\n".join(str(f) for f in active)


def test_repo_pallas_layer_is_clean():
    from repro.analysis.pallas_lint import audit_pallas
    active, _ = Allowlist().split(audit_pallas())
    assert active == [], "\n".join(str(f) for f in active)


def test_full_audit_is_clean_subprocess():
    """The CI gate itself: ``python -m repro.analysis --all`` exits 0
    (subprocess so the 8-device host override applies before jax loads)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-m", "repro.analysis", "--all"],
                       env=env, capture_output=True, text=True,
                       timeout=600, cwd=str(REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout
