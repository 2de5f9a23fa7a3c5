"""Layer 1 — jaxpr contract checks over the real entrypoints (RA1xx).

The auditor traces the actual shipped programs — the analog train step in
exact (shard_map) and GSPMD modes, the serve decode step (digital and
analog-backend variants), and the standalone ``xbar_sharded_update`` —
with ``jax.make_jaxpr`` over
``eval_shape`` state, so no parameter is ever materialised and no kernel
runs.  The contracts PRs 3–5 established as conventions become rules:

RA101  no f64/complex128 value anywhere in the traced program: one weak
       -type promotion in the analog chain silently doubles HBM and
       breaks the bit-exactness story across backends.
RA102  ``split_tapes`` containment: the differentiated tree holds tape
       slots only; g/ref/w_scale must live in the frozen tree (the
       symbolic-zero contract — a conductance leaf in the diff tree
       re-enters autodiff and the grads tree silently grows rank-2
       gradients the update path would shadow).  Every tape site carries
       the scale slot beside its code tapes (codes without their scales
       are no write operands), and the code tapes take the dtype that
       holds the write drivers' codes exactly (bfloat16 for the paper's
       coders: half the bytes of the stacked tapes).
RA103  collectives: the exact-mode shard_map body may contain no
       collective at all by default — the manual-collective read keeps
       conductances shard-local, so a bare ``all_gather`` in the body is
       now a finding (the legacy gather-then-replay read moved whole
       containers through exactly that shape).  Findings carry the repro
       source line, so the legitimate exchanges — the ordered partial-sum
       /output combine (``shardctx.combine_partials_exact``) and the
       order-exact 0/1 rail-metric psum — are allowlisted inline where
       they happen, each with its bit-exactness justification.
RA104  donation: the lowered step/decode entrypoints must alias their
       state/cache buffers (``tf.aliasing_output`` / buffer-donor
       markers in the lowering) — otherwise peak memory doubles.
RA105  the ADC sim chain stays de-pjit'd: zero pjit-wrapped clip/round
       equations (PR 3's −240-eqn win), and the step jaxpr stays under
       a total-equation budget so graph bloat is caught at trace time.
RA106  the *compiled* sharded module contains no order-sensitive
       collective (all-to-all / reduce-scatter / collective-permute) —
       counted via ``launch.hlo_analysis.count_collectives``; XLA is
       free to rewrite gathers, and a rewrite into a reduce-scatter
       pattern would reassociate the reduction order.
RA107  the *compiled* exact-mode train step moves no parameter-sized
       collective: every collective instance's operand must stay below
       the smallest sharded conductance block
       (``launch.hlo_analysis.collective_payloads``).  This is the
       compiled-HLO teeth behind the shard-local read — RA103 polices
       the traced program, but only the compiled module proves XLA did
       not reintroduce a full-container gather.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

from repro.analysis.findings import Finding, relativize

#: jaxpr primitive names that move data across mesh axes.
COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_to_all",
    "all_gather", "reduce_scatter", "psum_scatter", "pbroadcast",
    "pgather", "psum_invariant",
}

#: Collectives the exact-mode step body may contain without an inline
#: justification: none.  The shard-local read keeps conductances in
#: place; every remaining exchange (the ordered partial-sum combine, the
#: rail-metric psum) must carry an ``# audit: allow RA103 -- ...``
#: comment at its source line, so each collective in the body is either
#: a finding or an explicitly justified exception.
EXACT_MODE_WHITELIST: set = set()

#: RA105 budgets for the analog train step at the smoke geometry.
#: Measured after the read fusion: 0 pjit-wrapped clip/round, ~1.53k
#: recursive eqns unsharded.  The eqn ceiling has ~1.6x headroom — it
#: exists to catch per-layer unrolling (which multiplies eqns by
#: n_layers) and a de-fused read chain (which roughly doubles the
#: per-read eqn count), not drift.
MAX_PJIT_CLIP_ROUND = 0
MAX_STEP_EQNS = 2500

_SMOKE_ARCH = "lm100m"


def _jaxpr_types():
    from jax.extend import core as xc
    return xc.Jaxpr, xc.ClosedJaxpr


def _iter_eqns(jaxpr, inside_shard_map: bool = False):
    """Yield (eqn, inside_shard_map) over ``jaxpr`` and all sub-jaxprs."""
    Jaxpr, ClosedJaxpr = _jaxpr_types()
    for eqn in jaxpr.eqns:
        yield eqn, inside_shard_map
        inner = inside_shard_map or "shard_map" in eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr, inner)
                elif isinstance(sub, Jaxpr):
                    yield from _iter_eqns(sub, inner)


def _eqn_site(eqn) -> Tuple[Optional[str], Optional[int]]:
    """(repo-relative file, line) of an equation's user frame."""
    try:
        from jax._src import source_info_util as siu
        frame = siu.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return None, None
    return relativize(frame.file_name), frame.start_line


def check_no_f64(closed, entry: str) -> List[Finding]:
    import numpy as np
    bad = (np.float64, np.complex128)
    findings: List[Finding] = []
    for eqn, _ in _iter_eqns(closed.jaxpr):
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and dt in bad:
                f, ln = _eqn_site(eqn)
                findings.append(Finding(
                    "RA101", f"{eqn.primitive.name} produces {dt} "
                    "(x64/weak-type promotion in the traced program)",
                    file=f, line=ln, entry=entry))
    return findings


def check_collectives(closed, entry: str,
                      whitelist=EXACT_MODE_WHITELIST) -> List[Finding]:
    """RA103 on one traced program.  Collectives *outside* any shard_map
    cannot exist in these entrypoints either (they'd be unpartitioned
    pmap-style primitives), so every collective is checked; only
    whitelisted primitives inside shard_map bodies pass."""
    findings: List[Finding] = []
    for eqn, inside in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        if inside and name in whitelist:
            continue
        f, ln = _eqn_site(eqn)
        where = "inside" if inside else "outside"
        findings.append(Finding(
            "RA103", f"collective '{name}' {where} shard_map body "
            f"(whitelist: {sorted(whitelist)})",
            file=f, line=ln, entry=entry))
    return findings


def check_clip_round_budget(closed, entry: str,
                            max_pjit_clip_round: int = MAX_PJIT_CLIP_ROUND,
                            max_eqns: int = MAX_STEP_EQNS) -> List[Finding]:
    findings: List[Finding] = []
    n_eqns = 0
    pjit_wrapped: Dict[str, int] = {}
    for eqn, _ in _iter_eqns(closed.jaxpr):
        n_eqns += 1
        if eqn.primitive.name == "jit":
            sub = str(eqn.params.get("name", ""))
            if sub in ("clip", "round", "_clip", "_round", "amin", "amax"):
                pjit_wrapped[sub] = pjit_wrapped.get(sub, 0) + 1
    n_wrapped = sum(pjit_wrapped.values())
    if n_wrapped > max_pjit_clip_round:
        findings.append(Finding(
            "RA105", f"{n_wrapped} pjit-wrapped clip/round eqns "
            f"({pjit_wrapped}) — the ADC chain must stay primitive-level "
            "(use core.adc._clip/_round)", entry=entry))
    if n_eqns > max_eqns:
        findings.append(Finding(
            "RA105", f"step jaxpr has {n_eqns} equations "
            f"(budget {max_eqns}) — per-layer unrolling regression?",
            entry=entry))
    return findings


def check_donation(lowered_text: str, entry: str) -> List[Finding]:
    if "tf.aliasing_output" in lowered_text \
            or "jax.buffer_donor" in lowered_text:
        return []
    return [Finding(
        "RA104", "lowered entrypoint has no donated buffer "
        "(tf.aliasing_output / jax.buffer_donor absent) — the step's "
        "state is double-buffered", entry=entry)]


def check_tape_containment(diff, frozen, entry: str,
                           code_dtype=None) -> List[Finding]:
    """RA102 over the (diff, frozen) trees from ``split_tapes``;
    ``code_dtype``, where given, is the dtype the code tapes must have."""
    import numpy as np
    from repro.core.tiled_analog import TAPE_LEAVES

    findings: List[Finding] = []
    hoisted = ("g", "ref", "w_scale")

    def walk_diff(p, path):
        if isinstance(p, dict):
            if "x_tape" in p or "d_tape" in p:
                site = "/".join(path)
                leaked = sorted(set(p) - set(TAPE_LEAVES))
                if leaked:
                    findings.append(Finding(
                        "RA102", f"tape site {site} carries "
                        f"non-tape leaves {leaked} in the differentiated "
                        "tree (conductances re-enter autodiff)",
                        entry=entry))
                if "s_tape" not in p:
                    findings.append(Finding(
                        "RA102", f"tape site {site} carries code tapes "
                        "without their scale slot s_tape", entry=entry))
                wrong = sorted(k for k in ("x_tape", "d_tape")
                               if code_dtype is not None and k in p
                               and np.dtype(p[k].dtype)
                               != np.dtype(code_dtype))
                if wrong:
                    findings.append(Finding(
                        "RA102", f"tape site {site}: {wrong} are not "
                        f"{np.dtype(code_dtype).name}, the dtype that holds "
                        "the write drivers' codes", entry=entry))
            elif any(k in p for k in hoisted):
                found = sorted(k for k in hoisted if k in p)
                findings.append(Finding(
                    "RA102", f"{'/'.join(path)} holds {found} in the "
                    "differentiated tree — split_tapes failed to hoist",
                    entry=entry))
            else:
                for k, v in p.items():
                    walk_diff(v, path + (k,))

    def walk_frozen(p, path):
        if isinstance(p, dict):
            if any(k in p for k in hoisted):
                missing = sorted(k for k in hoisted if k not in p)
                if missing:
                    findings.append(Finding(
                        "RA102", f"frozen container {'/'.join(path)} "
                        f"missing {missing}", entry=entry))
                return
            for k, v in p.items():
                walk_frozen(v, path + (k,))

    walk_diff(diff, ())
    walk_frozen(frozen, ())
    return findings


# --------------------------------------------------------------------------
# Entry builders
# --------------------------------------------------------------------------

def _analog_cfg(arch: str = _SMOKE_ARCH):
    from repro.configs.registry import get_config
    return get_config(arch, smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_rows=64, analog_cols=64)


def _abstract_state(cfg):
    import jax
    from repro.train.analog_lm import init_state
    return jax.eval_shape(functools.partial(init_state, cfg=cfg),
                          jax.random.PRNGKey(0))


def _train_batch(cfg, batch: int = 2, seq: int = 16):
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    return {"tokens": S((batch, seq), jnp.int32),
            "labels": S((batch, seq), jnp.int32)}


def _key_struct():
    import jax
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _audit_unsharded_step(arch: str) -> List[Finding]:
    import jax
    from repro.core.xbar_ops import update_code_dtype
    from repro.train.analog_lm import AnalogTrainStep

    entry = f"train_step[{arch},exact,unsharded]"
    cfg = _analog_cfg(arch)
    step = AnalogTrainStep(cfg, lr=1e-3)
    state = _abstract_state(cfg)
    batch = _train_batch(cfg)
    key = _key_struct()

    closed = jax.make_jaxpr(step._step_impl)(state, batch, key)
    findings = check_no_f64(closed, entry)
    findings += check_collectives(closed, entry, whitelist=set())
    findings += check_clip_round_budget(closed, entry)
    findings += check_donation(
        step._step.lower(state, batch, key).as_text(), entry)
    diff, frozen = step.split_tapes(state["params"], batch)
    findings += check_tape_containment(diff, frozen, entry,
                                       update_code_dtype(step.xcfg))
    return findings


def _mesh_or_none(shape=(2, 2)):
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < int(np.prod(shape)):
        return None
    return make_mesh(shape, ("data", "model"))


def _audit_sharded_step(arch: str) -> List[Finding]:
    """Exact mode: the whole step body under shard_map on a 2x2 mesh."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.kernels.xbar_update import _wrap_shard_map
    from repro.train.analog_lm import AnalogTrainStep

    mesh = _mesh_or_none()
    if mesh is None:
        return [Finding(
            "RA103", "fewer than 4 devices — exact-mode shard_map body "
            "not audited (run via `python -m repro.analysis`, which sets "
            "the host-device override)", entry="train_step[sharded]")]
    entry = f"train_step[{arch},exact,2x2]"
    cfg = _analog_cfg(arch)
    step = AnalogTrainStep(cfg, lr=1e-3, mesh=mesh)
    state = _abstract_state(cfg)
    batch = _train_batch(cfg)
    key = _key_struct()

    # Mirror _build_sharded_step on abstract state: collect the container
    # specs, then wrap the body exactly as the shipped step does.
    step._cspecs = {}
    step._collect_cspecs(state["params"], ())
    state_sh = step.state_shardings(state)
    state_spec = jax.tree.map(lambda s: s.spec, state_sh)
    batch_spec = jax.tree.map(lambda _: P(), batch)
    fn = _wrap_shard_map(step._step_impl, mesh,
                         (state_spec, batch_spec, P()), (state_spec, P()))
    closed = jax.make_jaxpr(fn)(state, batch, key)
    findings = check_no_f64(closed, entry)
    findings += check_collectives(closed, entry)
    findings += check_clip_round_budget(closed, entry)
    return findings


def _audit_gspmd_step(arch: str) -> List[Finding]:
    """GSPMD mode (exact=False): sharded read path with replication pins;
    the only shard_map left is the nested rank-k write (no collectives)."""
    import jax
    from repro.core import shardctx
    from repro.train.analog_lm import AnalogTrainStep

    mesh = _mesh_or_none()
    if mesh is None:
        return []
    entry = f"train_step[{arch},gspmd,2x2]"
    cfg = _analog_cfg(arch)
    step = AnalogTrainStep(cfg, lr=1e-3, mesh=mesh, exact=False)
    state = _abstract_state(cfg)
    batch = _train_batch(cfg)
    key = _key_struct()
    prev = shardctx.get_shard_context()
    shardctx.set_shard_context(mesh, None)
    try:
        closed = jax.make_jaxpr(step._step_impl)(state, batch, key)
    finally:
        shardctx.set_shard_context(*prev)
    findings = check_no_f64(closed, entry)
    findings += check_collectives(closed, entry, whitelist=set())
    return findings


def _audit_serve_decode(arch: str) -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.serve.engine import ContinuousEngine

    entry = f"serve_decode[{arch}]"
    cfg = get_config(arch, smoke=True)
    params = jax.eval_shape(
        functools.partial(M.init_params, cfg=cfg), jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                           prefill_chunk=16)
    cache = jax.eval_shape(
        functools.partial(M.init_cache, cfg, 2, 64))
    tok = S((2,), jnp.int32)
    temps = S((2,), jnp.float32)
    key = _key_struct()

    closed = jax.make_jaxpr(eng._decode_impl)(params, cache, tok, key,
                                              temps)
    findings = check_no_f64(closed, entry)
    findings += check_collectives(closed, entry, whitelist=set())
    findings += check_donation(
        eng._decode.lower(params, cache, tok, key, temps).as_text(),
        entry)
    return findings


def _audit_analog_serve_decode(arch: str) -> List[Finding]:
    """The analog serving backend's decode step: conductance containers
    (programmed from digital weights, abstractly — no array ever
    materialises) flow through the tiled VMM sim inside the same
    ContinuousEngine decode jit the digital path uses.  Same contracts:
    no f64, no collectives at all, cache buffer donated."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    from repro.models import model as M
    from repro.serve.engine import ContinuousEngine

    entry = f"serve_decode[{arch},analog]"
    cfg = _analog_cfg(arch)
    params = jax.eval_shape(
        lambda key: M.program_digital(M.init_params(key, cfg.digital()),
                                      cfg),
        jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                           prefill_chunk=16)
    cache = jax.eval_shape(
        functools.partial(M.init_cache, cfg, 2, 64))
    tok = S((2,), jnp.int32)
    temps = S((2,), jnp.float32)
    key = _key_struct()

    closed = jax.make_jaxpr(eng._decode_impl)(params, cache, tok, key,
                                              temps)
    findings = check_no_f64(closed, entry)
    findings += check_collectives(closed, entry, whitelist=set())
    findings += check_donation(
        eng._decode.lower(params, cache, tok, key, temps).as_text(),
        entry)
    return findings


def _sharded_update_args():
    """A tiny tile-aligned container for the standalone update entry."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    from jax.sharding import PartitionSpec as P
    from repro.core import AdcConfig, CrossbarConfig, TAOX

    cfg = CrossbarConfig(rows=16, cols=16,
                         device=TAOX.replace(write_noise=0.5),
                         adc=AdcConfig(in_bits=4, out_bits=6))
    L, K, N, B = 2, 64, 32, 8
    specs = {"g": P(None, "model", None),
             "x_tape": P(None, None, "model"),
             "d_tape": P(None, None, None),
             "scale": P()}
    # The drives as the train step hands them over: the write drivers'
    # codes in bfloat16 (kernels/xbar_update.write_operands).
    args = (S((L, K, N), jnp.float32), S((L, B, K), jnp.bfloat16),
            S((L, B, N), jnp.bfloat16), S((L,), jnp.float32))
    return cfg, specs, args


def _audit_sharded_update() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.xbar_update import xbar_sharded_update

    mesh = _mesh_or_none()
    if mesh is None:
        return []
    entry = "xbar_sharded_update[2x2]"
    cfg, specs, args = _sharded_update_args()
    fn = functools.partial(xbar_sharded_update, cfg=cfg, mesh=mesh,
                           specs=specs, seed=jnp.uint32(7),
                           noise_mode="kernel", impl="fused")
    closed = jax.make_jaxpr(fn)(*args)
    findings = check_no_f64(closed, entry)
    # The rank-k write is fully local: nothing on the whitelist either.
    findings += check_collectives(closed, entry, whitelist=set())
    findings += _audit_compiled_update(fn, args, mesh, entry)
    return findings


def check_compiled_collectives(text: str, entry: str) -> List[Finding]:
    """RA106 on one compiled (or lowered) HLO module's text."""
    from repro.launch.hlo_analysis import count_collectives

    counts = count_collectives(text)
    banned = {k: counts[k] for k in
              ("all-to-all", "reduce-scatter", "collective-permute")
              if counts.get(k)}
    if banned:
        return [Finding(
            "RA106", f"compiled module contains order-sensitive "
            f"collectives {banned} (full mix: {counts})", entry=entry)]
    return []


def _audit_compiled_update(fn, args, mesh, entry: str) -> List[Finding]:
    """RA106: collective mix of the *compiled* sharded module."""
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    return check_compiled_collectives(text, entry)


def check_parameter_sized_collectives(text: str, min_param_bytes: int,
                                      entry: str) -> List[Finding]:
    """RA107 on one compiled HLO module's text: no collective instance
    may carry an operand at (or beyond) the smallest sharded conductance
    block — that is the signature of a full-container gather.  Partial
    sums and output combines scale with the token batch and sit well
    below the threshold."""
    from repro.launch.hlo_analysis import collective_payloads

    findings: List[Finding] = []
    for kind, nbytes in collective_payloads(text):
        if nbytes >= min_param_bytes:
            findings.append(Finding(
                "RA107", f"compiled exact-mode step moves a "
                f"parameter-sized collective: {kind} with {nbytes}-byte "
                f"operand (smallest sharded conductance block: "
                f"{min_param_bytes} bytes)", entry=entry))
    return findings


def _audit_compiled_sharded_step(arch: str) -> List[Finding]:
    """RA107: compile the exact-mode sharded step on a 2x2 mesh and
    threshold every collective instance against the smallest sharded
    conductance block.  A tiny token batch (1x4) keeps the compile cheap
    AND separates the scales: activation-sized combines land orders of
    magnitude under the parameter blocks, so the threshold has real
    margin instead of riding the smoke-shape coincidence."""
    import numpy as np
    from repro.train.analog_lm import AnalogTrainStep

    mesh = _mesh_or_none()
    if mesh is None:
        return []
    entry = f"train_step[{arch},exact,2x2,compiled]"
    cfg = _analog_cfg(arch)
    step = AnalogTrainStep(cfg, lr=1e-3, mesh=mesh)
    state = _abstract_state(cfg)
    batch = _train_batch(cfg, batch=1, seq=4)
    step._build_sharded_step(state, batch)
    text = step._step.lower(state, batch,
                            _key_struct()).compile().as_text()

    def _names(e):
        return () if e is None else (e if isinstance(e, tuple) else (e,))

    min_block = None
    for _path, (specs, gshape) in step._cspecs.items():
        shards = 1
        for e in specs["g"]:
            for a in _names(e):
                shards *= int(mesh.shape[a])
        if shards == 1:
            continue  # fully replicated: reads exactly as on one device
        blk = int(np.prod(gshape)) * 4 // shards
        min_block = blk if min_block is None else min(min_block, blk)
    if min_block is None:
        return []  # nothing sharded at this geometry: nothing to move
    return check_parameter_sized_collectives(text, min_block, entry)


def compiled_step_collectives(arch: str = _SMOKE_ARCH
                              ) -> Optional[Dict[str, int]]:
    """Collective counts of the compiled exact-mode train step — surfaced
    in BENCH_micro.json and usable ad hoc; not part of the default audit
    (compiling the full step costs ~a minute of CPU)."""
    import jax
    from repro.launch.hlo_analysis import count_collectives
    from repro.train.analog_lm import AnalogTrainStep

    mesh = _mesh_or_none()
    if mesh is None:
        return None
    cfg = _analog_cfg(arch)
    step = AnalogTrainStep(cfg, lr=1e-3, mesh=mesh)
    state = _abstract_state(cfg)
    batch = _train_batch(cfg)
    step._build_sharded_step(state, batch)
    text = step._step.lower(state, batch, _key_struct()).as_text()
    # Lowered (pre-XLA) text: counts the partitioner's *requested*
    # collectives; the compiled mix per module is RA106's job on the
    # update, which is cheap enough to compile in CI.
    return count_collectives(text)


def audit_jaxpr(arch: str = _SMOKE_ARCH) -> List[Finding]:
    findings: List[Finding] = []
    for builder in (_audit_unsharded_step, _audit_sharded_step,
                    _audit_gspmd_step, _audit_serve_decode,
                    _audit_analog_serve_decode):
        try:
            findings += builder(arch)
        except Exception as e:
            findings.append(Finding(
                "RA101", f"tracing failed: {type(e).__name__}: {e}",
                entry=getattr(builder, "__name__", str(builder))))
    try:
        findings += _audit_sharded_update()
    except Exception as e:
        findings.append(Finding(
            "RA106", f"tracing failed: {type(e).__name__}: {e}",
            entry="xbar_sharded_update"))
    try:
        findings += _audit_compiled_sharded_step(arch)
    except Exception as e:
        findings.append(Finding(
            "RA107", f"compile failed: {type(e).__name__}: {e}",
            entry=f"train_step[{arch},exact,2x2,compiled]"))
    return findings
