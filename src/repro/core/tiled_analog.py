"""Tiled-crossbar parameter containers for whole-model analog execution.

``core.analog_linear`` gives one layer on one logical array; this module is
the scaling story: any projection matrix of a transformer (q/k/v/o, the MLP
up/gate/down, MLA factors) is *programmed* onto a grid of physical
``rows x cols`` crossbar tiles and executed with the paper's three kernels —

    forward   = VMM   (parallel read,   Fig. 3a)
    backward  = MVM   (transpose read of the SAME conductances, Fig. 3b)
    update    = rank-k outer-product write (Fig. 3c)

The container is a plain dict pytree so it rides inside any model parameter
tree (including ``jax.lax.scan``-stacked per-layer trees):

    {"g": (K, N) conductances, "ref": (K, N) reference, "w_scale": ()}

Tiling is *physical*, not a storage layout: the read ops pad (K, N) to tile
multiples and quantise each tile's column charge independently
(``xbar_ops._tiled_read``), and the Pallas update kernel walks the same
grid.  ``tile_info`` reports the simulated grid (tests / diagnostics); the
hwmodel cost roll-up projects at the paper's Table-I geometry — see
``hwmodel/arch_cost.train_step_cost``.

In-situ training needs the *drive operands* of the outer-product write —
the write drivers' integer codes of the activations x and errors d, and
their two scales — not a materialised (K, N) gradient.  The custom VJP
here therefore returns **symbolic-zero** cotangents for g/ref/w_scale
(zero by type: nothing is traced, nothing is broadcast) and instead
writes the codes into two tape leaves and the scales into a third.  The
train step hoists the analog leaves out of the differentiated tree entirely
(:func:`split_tapes` / :func:`merge_tapes`), so the grads tree holds
exactly the tapes plus the digital gradients, and the analog optimizer
hands the tapes straight to the fused Pallas kernel
``kernels/xbar_update.py`` — the (K, N) gradient never exists in HBM; on
the hardware it never exists at all.

Sharding: on a device mesh the containers split at whole-tile granularity
(row-tiles over the FSDP axes, column-tiles over ``model`` —
``launch/sharding.analog_container_pspec``) and the tapes follow their
container's split, so each shard's rank-k write consumes only the tape
slices it owns.  The sharded train step is bit-identical to the
single-device step; the full pipeline narrative, including the
determinism contract, is in docs/analog_pipeline.md.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from .adc import AdcConfig
from .crossbar import CrossbarConfig, make_reference, tile_grid, \
    weights_to_conductance
from .device import IDEAL, LINEARIZED, TAOX, TAOX_NONOISE, DeviceConfig
from .shardctx import suspended_shard_context
from .xbar_ops import mvm, quantize_update_codes, vmm

Array = jax.Array

#: Device models selectable from a ModelConfig (``analog_device``).
DEVICE_MODELS: Dict[str, DeviceConfig] = {
    "ideal": IDEAL,
    "taox": TAOX,
    "taox-nonoise": TAOX_NONOISE,
    "linearized": LINEARIZED,
}


def device_model(name: str) -> DeviceConfig:
    """Resolve an ``analog_device`` name to a :class:`DeviceConfig`.

    Besides the registry keys, ``<base>:wn<mult>`` scales the base
    model's write noise by a float multiplier — e.g. ``taox:wn16`` is
    the TaOx device with 16x its calibrated write noise.  This is the
    nonideality axis the accuracy-recovery curve in
    ``benchmarks/analog_train_bench.py --curve`` sweeps.
    """
    if ":wn" in name:
        base, mult = name.split(":wn", 1)
        dev = DEVICE_MODELS[base]
        return dev.replace(write_noise=dev.write_noise * float(mult))
    return DEVICE_MODELS[name]


@lru_cache(maxsize=None)
def crossbar_from_model(cfg) -> CrossbarConfig:
    """Build the physical tile description from a (frozen) ModelConfig.

    Duck-typed on the ``analog_*`` fields so ``repro.core`` keeps zero
    dependency on ``repro.configs``; cached because the result is a static
    (hashable) argument of every jitted analog op.
    """
    return CrossbarConfig(
        rows=cfg.analog_rows, cols=cfg.analog_cols,
        device=device_model(cfg.analog_device),
        adc=AdcConfig(in_bits=cfg.analog_in_bits,
                      out_bits=cfg.analog_out_bits,
                      sat_sigmas=cfg.analog_sat_sigmas),
        read_impl=getattr(cfg, "analog_read_impl", "auto"),
        update_mode=getattr(cfg, "analog_update_mode", "outer"),
        carry=getattr(cfg, "analog_carry", False),
        carry_base=getattr(cfg, "analog_carry_base", 4.0))


def program_linear(w: Array, cfg: CrossbarConfig,
                   key: Optional[Array] = None,
                   w_max: Optional[float] = None) -> dict:
    """Program a digitally-initialised (K, N) weight matrix onto the grid.

    ``w_max`` fixes the weight<->conductance window; the default leaves
    8x-rms headroom so trained weights grow without pinning the rails (same
    policy as ``analog_linear_init``, but computed from the given weights
    so programming an existing digital checkpoint round-trips exactly).
    """
    w = w.astype(jnp.float32)
    if w_max is None:
        w_max = 8.0 * jnp.sqrt(jnp.mean(jnp.square(w)) + 1e-12)
    g, w_scale = weights_to_conductance(w, cfg, w_max=w_max)
    ref = make_reference(w.shape, cfg,
                         key=key if cfg.ref_sigma > 0 else None)
    p = {"g": g, "ref": ref, "w_scale": w_scale}
    if cfg.carry:
        # Periodic-carry LSB array, one significance level (1/carry_base)
        # below the primary.  Initialised at the reference (zero effective
        # contribution); a fresh buffer, not an alias of ref, so donation
        # never sees the same buffer twice.
        p["g_carry"] = ref + jnp.zeros_like(ref)
    return p


def program_stacked(w: Array, cfg: CrossbarConfig,
                    w_max: Optional[float] = None) -> dict:
    """Program a stack of weight matrices — (E, K, N) expert stacks or any
    deeper lead dims — onto per-matrix tile grids.  Each matrix gets its
    own calibration (``w_max``/``w_scale``), exactly as if programmed
    alone: on the hardware every expert owns its own arrays."""
    if w.ndim == 2:
        return program_linear(w, cfg, w_max=w_max)
    return jax.vmap(lambda ww: program_stacked(ww, cfg, w_max=w_max))(w)


def is_analog_container(p) -> bool:
    return isinstance(p, dict) and {"g", "ref", "w_scale"} <= set(p)


def effective_g(p: dict, cfg: CrossbarConfig) -> Array:
    """Conductances the read path sees: the primary array plus, when the
    container carries a periodic-carry LSB array, its signed deviation
    scaled one significance level down (paper §V.C stack read — both
    cells drive the shared bit line, the carry cell at 1/base drive).
    Containers without ``g_carry`` pass through untouched."""
    gc = p.get("g_carry")
    if gc is None:
        return p["g"]
    with jax.named_scope("xbar.carry"):
        return p["g"] + (gc - p["ref"]) / cfg.carry_base


def readout(p: dict, cfg: CrossbarConfig) -> Array:
    """Digital serial read of the programmed weights (paper §III.D).

    Handles scan-stacked containers, where ``g`` is (L, K, N) and
    ``w_scale`` is (L,), and folds in any periodic-carry residual so a
    mid-training checkpoint reads back the weights the model executes.
    """
    w_scale = jnp.asarray(p["w_scale"])[..., None, None]
    return (effective_g(p, cfg) - p["ref"]) / w_scale


def tile_info(p: dict, cfg: CrossbarConfig) -> Tuple[int, int, float]:
    """(tiles_k, tiles_n, fill fraction) of the grid holding this layer."""
    k, n = p["g"].shape[-2:]
    tk, tn = tile_grid(k, n, cfg)
    return tk, tn, (k * n) / (tk * tn * cfg.rows * cfg.cols)


# --------------------------------------------------------------------------
# Taped analog matmul: the in-situ training primitive.
# --------------------------------------------------------------------------

def _symbolic_zero(x: Array) -> SymbolicZero:
    """A cotangent that is zero *by type*: no array is traced, nothing is
    broadcast, nothing hits HBM.  (g/ref/w_scale are f32, so the tangent
    aval equals the primal aval.)"""
    return SymbolicZero(jax.core.ShapedArray(jnp.shape(x),
                                             jnp.result_type(x)))


def _vmm_any(x: Array, g: Array, ref: Array, w_scale, cfg,
             meta=None, rows=None) -> Array:
    """VMM for a plain (K, N) container or an expert-batched (E, K, N)
    stack (x then carries a matching leading dim: one activation batch per
    expert's array).  With a ``meta`` (exact-mode manual-collective read)
    the read is shard-local and handles lead dims itself; otherwise the
    batched read runs with the shard context suspended — each expert's
    array is read whole on its owner; the GSPMD-exact-reduce pins only
    apply to tile-sharded single arrays.

    Everything a read does on the device, from the DAC full-scale
    reduction to the slice after the kernel, is named ``xbar.read``
    (the write's driver quantisation and kernel ``xbar.write``, the
    carry blend and sweep ``xbar.carry``): the chip benchmark splits a
    profiler trace by these names (``benchmarks/chip/scopes.py``).

    ``rows`` (an expert-batched stack's routed row counts, one per
    expert) lets the kernel skip each expert's zero rows past its count."""
    with jax.named_scope("xbar.read"):
        if meta is not None:
            return vmm(x, g, ref, w_scale, cfg, meta=meta)
        if g.ndim == 2:
            return vmm(x, g, ref, w_scale, cfg)
        with suspended_shard_context():
            # vmm takes the lead dims natively: the fused read flattens
            # them onto its kernel layer grid (one pallas_call per
            # container on TPU); the chain oracle vmaps per matrix.
            return vmm(x, g, ref, w_scale, cfg, rows=rows)


def _mvm_any(d: Array, g: Array, ref: Array, w_scale, cfg,
             meta=None, rows=None) -> Array:
    with jax.named_scope("xbar.read"):
        if meta is not None:
            return mvm(d, g, ref, w_scale, cfg, meta=meta)
        if g.ndim == 2:
            return mvm(d, g, ref, w_scale, cfg)
        with suspended_shard_context():
            return mvm(d, g, ref, w_scale, cfg, rows=rows)


def _quantize_operands_any(x: Array, d: Array, cfg):
    """Write-driver quantisation, per matrix of a batched container: the
    full-scale calibration of the temporal/voltage coders is per physical
    array, so each expert quantises against its own operand range.
    Returns the integer codes and each matrix's (x_scale, d_scale) pair
    along a last dim of 2."""

    def codes(xx, dd):
        x_int, d_int, x_scale, d_scale = quantize_update_codes(xx, dd, cfg)
        return x_int, d_int, jnp.stack([x_scale, d_scale])

    with jax.named_scope("xbar.write"):
        return codes(x, d) if x.ndim == 2 else jax.vmap(codes)(x, d)


@partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _taped_matmul(g: Array, ref: Array, w_scale: Array,
                  x_tape: Array, d_tape: Array, s_tape: Array, n_tape, rows,
                  x: Array, cfg: CrossbarConfig, meta, tape_dtypes) -> Array:
    """y = read(x); its VJP deposits the write drivers' codes in the
    tapes (in ``tape_dtypes``, the slots' own), their scales in
    ``s_tape``, and for an expert-batched stack its routed row counts
    ``rows`` in ``n_tape`` (both ``None`` for other containers)."""
    del x_tape, d_tape, s_tape, n_tape
    return _vmm_any(x, g, ref, w_scale, cfg, meta, rows)


def _value(a):
    return None if a is None else a.value


def _taped_fwd(g, ref, w_scale, x_tape, d_tape, s_tape, n_tape, rows, x,
               cfg, meta, tape_dtypes):
    # defvjp(..., symbolic_zeros=True) wraps every differentiable primal as
    # CustomVJPPrimal(value, perturbed); the tapes' values are never read.
    del x_tape, d_tape, s_tape, n_tape, tape_dtypes
    g, ref, w_scale, x = g.value, ref.value, w_scale.value, x.value
    rows = _value(rows)
    y = _vmm_any(x, g, ref, w_scale, cfg, meta, rows)
    return y, (g, ref, w_scale, rows, x)


def _taped_bwd(cfg, meta, tape_dtypes, res, dy):
    g, ref, w_scale, rows, x = res
    if isinstance(dy, SymbolicZero):  # y unused downstream: nothing flows
        dy = jnp.zeros(dy.aval.shape, dy.aval.dtype)
    dy32 = dy.astype(jnp.float32)
    # Error backprop: transpose read of the SAME (quantised, saturated,
    # ADC'd) conductances the forward pass saw.  Rows past an expert's
    # count carry a zero error, as they carried a zero drive.
    dx = _mvm_any(dy32, g, ref, w_scale, cfg, meta, rows)
    # The write drivers' operands, quantised exactly as the hardware does
    # (rows: temporal code, columns: voltage code).  Their codes flow out
    # through the tape leaves and their scales through ``s_tape``;
    # g/ref/w_scale get *symbolic* zero cotangents — the dense (K, N)
    # gradient is never formed, not even as a zeros fill.
    x_int, d_int, scales = _quantize_operands_any(x.astype(jnp.float32),
                                                  dy32, cfg)
    if rows is None:
        n_q = rows_ct = None
    else:  # the counts ride to the write through the n_tape cotangent
        n_q, rows_ct = rows.astype(jnp.float32), _symbolic_zero(rows)
    return (_symbolic_zero(g), _symbolic_zero(ref), _symbolic_zero(w_scale),
            x_int.astype(tape_dtypes[0]), d_int.astype(tape_dtypes[1]), scales,
            n_q, rows_ct, dx.astype(x.dtype))


_taped_matmul.defvjp(_taped_fwd, _taped_bwd, symbolic_zeros=True)


def _tape_slots(p: dict, rows: tuple, k: int, n: int) -> tuple:
    """The container's (x_tape, d_tape, s_tape), or throwaway zero slots
    for ``rows`` operand rows where it carries none."""
    names = ("x_tape", "d_tape", "s_tape")
    if not all(name in p for name in names):
        p = {**make_tapes({"g": jax.ShapeDtypeStruct((k, n), jnp.float32)},
                          rows), **p}
    return tuple(p[name] for name in names)


def analog_project(p: dict, x: Array, cfg: CrossbarConfig) -> Array:
    """Apply a programmed container to activations of shape (..., K).

    If the container carries tape leaves (injected by the analog train
    step), the backward pass deposits the write drivers' codes and scales
    there; otherwise throwaway zero tapes are created (inference / eval —
    no backward, no cost).

    Each container must be applied at most once per differentiated step:
    cotangents of a reused container would *sum* the tapes, which is not
    the operand of the summed outer product.  Dense transformer stacks
    apply each projection exactly once per token batch.
    """
    lead = x.shape[:-1]
    meta = p.get("tp_meta")
    # Exact-mode sharded containers hold local tile blocks; activations and
    # tapes are globally shaped, so geometry comes from the static meta.
    k, n = meta.view(2) if meta is not None else p["g"].shape
    xb = x.reshape(-1, k)
    x_tape, d_tape, s_tape = _tape_slots(p, (xb.shape[0],), k, n)
    # audit: allow RA103 -- ordered partial-sum/output combines of the shard-local read (shardctx.combine_partials_exact, anchored here by the custom_vjp call site): arithmetic-free activation-sized gathers in pinned order; RA107 bounds their compiled byte size
    y = _taped_matmul(effective_g(p, cfg), p["ref"], p["w_scale"], x_tape,
                      d_tape, s_tape, None, None, xb.astype(jnp.float32),
                      cfg, meta, (x_tape.dtype, d_tape.dtype))
    return y.reshape(*lead, n).astype(x.dtype)


def analog_project_batched(p: dict, x: Array, cfg: CrossbarConfig,
                           rows: Optional[Array] = None) -> Array:
    """Apply an expert-batched container (g: (E, K, N)) to expert-batched
    activations x: (E, T, K) -> (E, T, N).

    Each expert's matrix is its own physical tile grid reading its own
    dispatch rows — one application of the whole stack per step, so the
    tape leaves ((E, T, K)/(E, T, N)) carry exactly the per-expert write
    operands and the stack updates as extra layers of the layer-batched
    rank-k write (``core.analog_registry.flatten_lead``).

    ``rows`` (E,): each expert's routed rows, the first of its T (the
    rest are zero).  The reads skip the rest, and the backward pass
    leaves the counts in the container's ``n_tape`` slot for the write.
    """
    meta = p.get("tp_meta")
    e, k, n = meta.view(3) if meta is not None else p["g"].shape
    if x.shape[0] != e or x.shape[-1] != k:
        raise ValueError(f"expert-batched x {x.shape} does not match "
                         f"container {p['g'].shape}")
    x_tape, d_tape, s_tape = _tape_slots(p, x.shape[:2], k, n)
    n_tape = p.get("n_tape")
    if rows is not None:
        rows = rows.astype(jnp.float32)
        if n_tape is None:
            n_tape = jnp.zeros((e,), jnp.float32)
    else:
        n_tape = None
    # audit: allow RA103 -- ordered EP-dispatch/partial-sum combines of the shard-local expert read (shardctx.combine_partials_exact, anchored here by the custom_vjp call site): arithmetic-free capacity-buffer gathers in pinned order; RA107 bounds their compiled byte size
    y = _taped_matmul(effective_g(p, cfg), p["ref"], p["w_scale"], x_tape,
                      d_tape, s_tape, n_tape, rows, x.astype(jnp.float32),
                      cfg, meta, (x_tape.dtype, d_tape.dtype))
    return y.astype(x.dtype)


#: The in-step tape slots of a container: the write drivers' codes, their
#: (x_scale, d_scale) pair per application, and an expert-batched stack's
#: routed row counts.
TAPE_LEAVES = ("x_tape", "d_tape", "s_tape", "n_tape")


def pop_tapes(params):
    """Strip the tape leaves off every container in a (sub)tree.

    Returns ``(clean, tapes, found)``: ``clean`` is the tree without
    the tape leaves, ``tapes`` mirrors it with dicts of them at container
    sites (empty dicts elsewhere), ``found`` says whether any tape leaf
    existed.  Used by the hybrid stack to turn the shared block's
    per-application tape dim into scan xs — each group boundary consumes
    its own slice (:func:`push_tapes`) so a weight set applied G times per
    step tapes G distinct operand blocks.
    """
    if is_analog_container(params):
        tapes = {k: params[k] for k in TAPE_LEAVES if k in params}
        clean = {k: v for k, v in params.items() if k not in TAPE_LEAVES}
        return clean, tapes, bool(tapes)
    if isinstance(params, dict):
        out = {k: pop_tapes(v) for k, v in params.items()}
        return ({k: v[0] for k, v in out.items()},
                {k: v[1] for k, v in out.items()},
                any(v[2] for v in out.values()))
    return params, {}, False


def push_tapes(params, tapes):
    """Inverse of :func:`pop_tapes`: re-inject (sliced) tape leaves next
    to their containers."""
    if is_analog_container(params):
        return {**params, **tapes}
    if isinstance(params, dict):
        return {k: push_tapes(v, tapes.get(k, {})) for k, v in params.items()}
    return params


def make_tapes(p: dict, n_tokens, counted: bool = False,
               code_dtype=jnp.float32) -> dict:
    """Zero tape *slots* for one container (shapes (T, K) / (T, N), and
    (2,) for the scale pair).

    Tape lifecycle: the train step allocates these slots (inside jit they
    are zero constants whose values are never read — the taped VJP ignores
    them and XLA folds them away, so no (T, K) buffer is ever written), the
    backward pass of ``_taped_matmul`` overwrites their cotangents with the
    write drivers' integer codes (in ``code_dtype``: the train step's
    ``xbar_ops.update_code_dtype``, bfloat16 for the paper's coders) and
    each application's (x_scale, d_scale) pair, and the analog optimizer
    consumes those cotangents as the drive operands of the fused parallel
    write (``kernels/xbar_update.write_operands``).  One allocation site,
    one writer, one consumer.

    ``n_tokens`` may be a tuple: the operand-row shape between the
    container's own lead dims and the feature dim — ``(T,)`` for the
    ordinary once-per-step application, ``(reps, T)`` for a weight set
    applied ``reps`` times per step (the hybrid shared block), or the
    per-expert ``(T,)`` of an expert-batched container (see
    ``core.analog_registry.tape_lead``).  ``counted`` adds the ``n_tape``
    slot, one per matrix, for the routed row counts of an expert-batched
    stack.
    """
    meta = p.get("tp_meta")
    # Tapes are replicated operand buffers: size them from the container's
    # *global* geometry when the container holds local shard blocks.
    gshape = meta.shape if meta is not None else p["g"].shape
    k, n = gshape[-2:]
    lead = gshape[:-2]  # scan-stacked containers carry (L, K, N)
    rows = n_tokens if isinstance(n_tokens, tuple) else (n_tokens,)
    tapes = {"x_tape": jnp.zeros((*lead, *rows, k), code_dtype),
             "d_tape": jnp.zeros((*lead, *rows, n), code_dtype),
             "s_tape": jnp.zeros((*lead, *rows[:-1], 2), jnp.float32)}
    if counted:
        tapes["n_tape"] = jnp.zeros(lead, jnp.float32)
    return tapes


def with_tapes(params, n_tokens: int, tokens_for=None, path=()):
    """Recursively inject tape leaves next to every analog container.

    ``tokens_for(path, g_shape)`` optionally resolves the per-container
    operand-row shape (shared-block reps); the default is ``n_tokens``
    rows everywhere, which is correct for trees whose every container is
    applied once to the full token batch.

    Prefer :func:`split_tapes` in training code — differentiating a
    ``with_tapes`` tree asks for cotangents of every g/ref/w_scale leaf,
    which ``jax.grad`` then instantiates as dense zeros at the boundary.
    """
    if is_analog_container(params):
        rows = tokens_for(path, params["g"].shape) if tokens_for \
            else n_tokens
        return {**params, **make_tapes(params, rows)}
    if isinstance(params, dict):
        return {k: with_tapes(v, n_tokens, tokens_for, path + (k,))
                for k, v in params.items()}
    return params


def split_tapes(params, n_tokens: int, tokens_for=None, path=(),
                counted=None, code_dtype=jnp.float32):
    """Partition a parameter tree for the hoisted analog gradient.

    Returns ``(diff, frozen)``: ``diff`` carries every digital leaf plus,
    for each analog container, only the tape slots; ``frozen`` mirrors the
    tree with each container's g/ref/w_scale (``None`` elsewhere).
    ``jax.value_and_grad`` over ``diff`` (recombined via
    :func:`merge_tapes` inside the loss closure) therefore never requests a
    conductance cotangent — the grads tree holds exactly the tapes and the
    digital gradients, and no (K, N) zero array exists even at the jaxpr
    level (the taped VJP emits symbolic zeros internally).

    ``tokens_for``: per-container operand-row resolver, as in
    :func:`with_tapes` — the analog train step passes the registry's
    family-aware resolver so MoE expert tapes hold the dropless buffer
    and the hybrid shared block tapes one slot per group application.
    ``counted(path)`` says which containers take an ``n_tape`` slot, and
    ``code_dtype`` is the dtype of the code tapes (:func:`make_tapes`).
    """
    if is_analog_container(params):
        rows = tokens_for(path, params["g"].shape) if tokens_for \
            else n_tokens
        return (make_tapes(params, rows,
                           counted=bool(counted and counted(path)),
                           code_dtype=code_dtype),
                {k: params[k]
                 for k in ("g", "ref", "w_scale", "g_carry", "tp_meta")
                 if k in params})
    if isinstance(params, dict):
        split = {k: split_tapes(v, n_tokens, tokens_for, path + (k,),
                                counted, code_dtype)
                 for k, v in params.items()}
        return ({k: v[0] for k, v in split.items()},
                {k: v[1] for k, v in split.items()})
    return params, None


def merge_tapes(diff, frozen):
    """Inverse of :func:`split_tapes`: rebuild the tree the model consumes
    (each analog container regains its g/ref/w_scale next to its tapes)."""
    if frozen is None:
        return diff
    if isinstance(frozen, dict) and "g" in frozen:
        return {**frozen, **diff}
    return {k: merge_tapes(diff[k], frozen[k]) for k in diff}
