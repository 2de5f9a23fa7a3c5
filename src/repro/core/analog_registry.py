"""Family-agnostic analog parameter registry.

One module owns the mapping from a *parameter path* + shape + consumer
kind to everything the analog pipeline needs to know about the matrix
living there:

  * whether it belongs on crossbar tiles at all (vs the digital core),
  * the **consumer kind** — column-parallel producer, row-parallel
    consumer, or expert-batched stack — which fixes
  * the container's **sharding layout** (which dims carry FSDP / TP / EP
    tile splits, at what granularity),
  * its **tape route**: how many write-driver operand rows the backward
    pass deposits per step (MoE expert tapes hold a dropless buffer of
    every token per expert, with the routed row counts beside them;
    shared hybrid blocks tape once per group application), and
  * its **update view**: how the (possibly expert-batched) container
    flattens onto the layer-batched rank-k write grid of
    ``kernels/xbar_update.py`` so the whole stack updates in one
    ``pallas_call``.

Consumers: ``models/layers.py`` / ``models/moe.py`` / ``models/ssm.py``
build containers through it, ``launch/sharding.py`` translates its
logical layouts onto a concrete mesh, ``train/analog_lm.py`` routes
tapes and updates with it, and ``hwmodel/arch_cost.py`` enumerates the
tile/energy/area roll-up from it — nobody hand-walks the parameter tree
with per-family rules anymore.

The module is duck-typed on the ``analog_*`` / MoE / hybrid fields of a
ModelConfig (like ``core.tiled_analog``) so ``repro.core`` keeps zero
dependency on ``repro.configs``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Consumer kinds
# ---------------------------------------------------------------------------

#: Producer: activations drive the rows, output columns split under TP.
COLUMN_PARALLEL = "column_parallel"
#: Consumer: the projection reduces a TP-split feature dim (wo/w_down/...).
ROW_PARALLEL = "row_parallel"
#: A stack of per-expert matrices applied to expert-batched activations
#: (MoE dispatch buffers); the expert dim is the EP axis.
EXPERT_BATCHED = "expert_batched"

KINDS = (COLUMN_PARALLEL, ROW_PARALLEL, EXPERT_BATCHED)

#: Leaf names of a tiled-crossbar container (plus the in-step tape slots).
#: ``g_carry`` is the optional periodic-carry LSB array (paper §V.C) —
#: present only when the config enables carry, shaped and sharded exactly
#: like ``g``.
ANALOG_LEAVES = ("g", "ref", "w_scale", "g_carry", "x_tape", "d_tape",
                 "n_tape")

#: Projection keys whose K (row) tiles follow the TP axis — the analog
#: mirror of the digital row-parallel rule.
ROW_PARALLEL_KEYS = frozenset({"wo", "w_down", "out_proj"})
#: Column-parallel producers (fused layouts included: a concat of
#: column-parallel pieces is itself column-parallel).
COLUMN_PARALLEL_KEYS = frozenset({
    "wq", "wk", "wv", "wqkv", "w_up", "w_gate", "w_upgate",
    "wkv_a", "wkv_b", "in_proj", "shared_in",
})
PROJECTION_KEYS = ROW_PARALLEL_KEYS | COLUMN_PARALLEL_KEYS

#: The dict key under which MoE stacks its per-expert matrices.
EXPERT_STACK_KEY = "experts"

#: Matrix-shaped parameters the paper deliberately keeps on the digital
#: core: embeddings, the logits head, the (tiny) MoE router, encoder
#: positional tables, and the SSD depthwise conv.
DIGITAL_CORE_KEYS = frozenset({
    "embed", "lm_head", "router", "enc_pos", "conv_w", "conv_b",
})

#: Non-matmul leaf names (norm gains, SSD scalars, block gates).  They are
#: vectors per layer, but scan-stacking makes them 2-D, so the digital
#: triage must know them by name rather than by rank.
DIGITAL_LEAF_NAMES = frozenset({
    "scale", "a_log", "d_skip", "dt_bias", "gate_attn", "gate_ffn",
})

#: Weight sets of the hybrid (Zamba-2) *shared* block: one parameter set
#: applied at every group boundary, so its containers see
#: ``n_layers // attn_every`` applications per step (-> tape reps).
SHARED_BLOCK_KEYS = frozenset({"shared_in", "shared_attn", "shared_ffn"})


def _keys(path: Sequence) -> Tuple[str, ...]:
    """Normalise a tree path to plain strings, dropping container-leaf
    names and the digital ``"w"`` wrapper so callers can pass either the
    container path or any leaf path under it."""
    out = []
    for k in path:
        s = str(getattr(k, "key", getattr(k, "idx", k)))
        if s not in ANALOG_LEAVES and s != "w":
            out.append(s)
    return tuple(out)


def classify(path: Sequence) -> str:
    """Consumer kind of the container at ``path`` (any leaf path under it
    works too).  Expert stacks win over the per-matrix orientation: an
    expert ``w_down`` is updated/sharded as an expert-batched container,
    matching the digital EP rule (the expert dim consumes the TP axis)."""
    keys = _keys(path)
    if EXPERT_STACK_KEY in keys:
        return EXPERT_BATCHED
    proj = next((k for k in reversed(keys) if k in PROJECTION_KEYS), None)
    if proj in ROW_PARALLEL_KEYS:
        return ROW_PARALLEL
    return COLUMN_PARALLEL


def classify_param(path: Sequence) -> Optional[str]:
    """Crossbar-vs-digital triage of one matrix-shaped parameter.

    Returns a consumer kind for crossbar-mapped projections, ``"digital"``
    for parameters the paper keeps on the digital core, and ``None`` for
    matrices this registry cannot place — callers in device mode must
    treat ``None`` as an error (see ``hwmodel/arch_cost``), never silently
    as digital compute.
    """
    keys = _keys(path)
    if any(k in DIGITAL_CORE_KEYS for k in keys):
        return "digital"
    if keys and keys[-1] in DIGITAL_LEAF_NAMES:
        return "digital"
    if EXPERT_STACK_KEY in keys:
        return EXPERT_BATCHED
    proj = next((k for k in reversed(keys) if k in PROJECTION_KEYS), None)
    if proj is None:
        return None
    return ROW_PARALLEL if proj in ROW_PARALLEL_KEYS else COLUMN_PARALLEL


# ---------------------------------------------------------------------------
# Tape route: operand rows per step and applications per step
# ---------------------------------------------------------------------------

def tape_reps(path: Sequence, cfg) -> int:
    """How many times the container at ``path`` is applied per step.

    The hybrid shared block is one weight set applied at every group
    boundary; its tapes carry a leading ``reps`` dim (one slot per
    application) that the update collapses into the token contraction —
    the summed outer product over all applications is exactly the rank-k
    write a reused array receives.
    """
    keys = _keys(path)
    if getattr(cfg, "attn_every", 0) and \
            any(k in SHARED_BLOCK_KEYS for k in keys):
        return cfg.n_layers // cfg.attn_every
    return 1


def operand_rows(path: Sequence, cfg, n_tokens: int,
                 batch_shape: Optional[Tuple[int, ...]] = None) -> int:
    """How many operand rows one application of this container sees.

    Most containers are driven by the decoder token batch (``n_tokens``).
    The exceptions ride the model's second streams: audio *encoder*
    containers see the frame batch, and the fused cross-attention
    ``wqkv`` array is driven by BOTH streams concatenated (decoder tokens
    + vision patches / encoder frames) in its single application.
    ``batch_shape`` is the (B, S) of the token batch (needed to scale the
    per-sequence stream lengths to the batch).
    """
    keys = _keys(path)
    b = batch_shape[0] if batch_shape else 1
    stream = b * (getattr(cfg, "n_vision_tokens", 0)
                  or getattr(cfg, "n_audio_frames", 0))
    if "enc_layers" in keys:
        return b * cfg.n_audio_frames
    if "xattn" in keys:
        if keys[-1] == "wqkv":
            return n_tokens + stream
        if keys[-1] in ("wk", "wv"):  # legacy split cross layout
            return stream
    return n_tokens


def tape_lead(path: Sequence, cfg, n_tokens: int,
              batch_shape: Optional[Tuple[int, ...]] = None
              ) -> Tuple[int, ...]:
    """Shape of one container's tape slots *between* the container's own
    lead dims and the operand feature dim: ``(T,)`` for a once-applied
    container (T from :func:`operand_rows`), ``(reps, T)`` for the shared
    hybrid block.  An expert-batched container's slots are ``(T,)`` per
    expert too: the device-mode dispatch is dropless, each expert's
    buffer holding up to every token (``models/moe``)."""
    if classify(path) == EXPERT_BATCHED:
        return (n_tokens,)
    rows = operand_rows(path, cfg, n_tokens, batch_shape)
    reps = tape_reps(path, cfg)
    return (reps, rows) if reps > 1 else (rows,)


# ---------------------------------------------------------------------------
# Sharding layout (logical; launch/sharding maps logical axes to the mesh)
# ---------------------------------------------------------------------------

def leaf_layout(kind: str, ndim: int, leaf: str, rows: int, cols: int
                ) -> Tuple[Tuple[Optional[str], int], ...]:
    """Per-dim ``(logical_axis, granularity)`` of one container leaf.

    Logical axes: ``"fsdp"`` (the data/pod axes), ``"tp"`` (the model
    axis), ``"ep"`` (expert parallelism — also the model axis, which the
    expert dim consumes, so expert matrices' inner dims only FSDP-shard,
    mirroring the digital EP rule).  Granularity is the tile size the dim
    may only split at (1 for non-tiled dims).  ``None`` = replicated.

    The layer dim of a scan-stacked container is never sharded (it is the
    scan axis); ``w_scale`` shards exactly like its container's lead dims
    (per-expert scales follow their experts).
    """
    lead = ndim if leaf == "w_scale" else ndim - 2
    roles: list = [(None, 1)] * lead
    if kind == EXPERT_BATCHED and lead >= 1:
        roles[lead - 1] = ("ep", 1)
    if leaf == "w_scale":
        return tuple(roles)
    if kind == EXPERT_BATCHED:
        r, c = ("fsdp", rows), (None, 1)
    elif kind == ROW_PARALLEL:
        r, c = ("tp", rows), ("fsdp", cols)
    else:
        r, c = ("fsdp", rows), ("tp", cols)
    if leaf in ("g", "ref", "g_carry"):
        return (*roles, r, c)
    if leaf == "x_tape":
        return (*roles, (None, 1), r)
    if leaf == "d_tape":
        return (*roles, (None, 1), c)
    raise KeyError(f"unknown container leaf {leaf!r}")


# ---------------------------------------------------------------------------
# Update view: flattening onto the layer-batched rank-k write grid
# ---------------------------------------------------------------------------

def hoist_axis(kind: str, g_ndim: int) -> Optional[int]:
    """Lead dim moved outermost before flattening onto the kernel's layer
    grid: the expert dim of a scan-stacked expert container (so an
    EP-sharded block is a *contiguous* range of flattened layer indices
    and the counter-PRNG lead offset stays a single scalar).  ``None``
    when the natural order already satisfies that (everything else)."""
    lead = g_ndim - 2
    if kind == EXPERT_BATCHED and lead >= 2:
        return lead - 1
    return None


def flatten_lead(kind: str, g, x_tape, d_tape, scale, noise=None):
    """Collapse a container's lead dims (and any extra tape-rep dims) onto
    the kernel's single layer axis / token axis.

    ``g``: (lead..., K, N); tapes: (lead..., reps?, T, K|N); ``scale``:
    (lead...,).  Returns ``(g3, x3, d3, scale1, noise3, unflatten)`` with
    ``g3`` (Lflat, K, N) — expert dim outermost for expert-batched kinds —
    and ``unflatten`` mapping the updated (Lflat, K, N) conductances back
    to the container's layout.  2-D containers pass through (the kernel
    handles them natively); their extra tape-rep dims still collapse into
    the token axis (the summed outer product over applications).
    """
    import jax.numpy as jnp

    lead = g.ndim - 2
    if lead == 0:
        # 2-D container: collapse tape reps into tokens, nothing else
        x3 = x_tape.reshape(-1, x_tape.shape[-1])
        d3 = d_tape.reshape(-1, d_tape.shape[-1])
        return g, x3, d3, scale, noise, lambda gg: gg

    hoist = hoist_axis(kind, g.ndim)

    def move(a):
        # a: (lead..., rest...); hoist one lead dim to the front
        return jnp.moveaxis(a, hoist, 0) if hoist is not None else a

    g_shape = g.shape
    gm = move(g)
    xm = move(x_tape)
    dm = move(d_tape)
    sm = move(scale) if scale.ndim == lead and lead else scale
    nm = move(noise) if noise is not None else None

    lflat = int(np.prod(gm.shape[:lead]))
    g3 = gm.reshape(lflat, *gm.shape[lead:])
    x3 = xm.reshape(lflat, -1, xm.shape[-1])
    d3 = dm.reshape(lflat, -1, dm.shape[-1])
    s1 = jnp.broadcast_to(sm, gm.shape[:lead]).reshape(lflat)
    n3 = nm.reshape(lflat, *nm.shape[lead:]) if nm is not None else None

    def unflatten(gg):
        gg = gg.reshape(*gm.shape[:lead], *gg.shape[-2:])
        if hoist is not None:
            gg = jnp.moveaxis(gg, 0, hoist)
        return gg.reshape(g_shape)

    return g3, x3, d3, s1, n3, unflatten


def flatten_counts(kind: str, v):
    """Per-matrix values of a container's lead dims (an expert-batched
    stack's routed row counts, shaped like ``w_scale``) in the flattened
    layer order of :func:`flatten_lead`."""
    import jax.numpy as jnp
    hoist = hoist_axis(kind, v.ndim + 2)
    if hoist is not None:
        v = jnp.moveaxis(v, hoist, 0)
    return v.reshape(-1)


# ---------------------------------------------------------------------------
# Device-mode tree validation
# ---------------------------------------------------------------------------

def _is_container(p) -> bool:
    from .tiled_analog import is_analog_container
    return is_analog_container(p)


def validate_device_params(params, cfg) -> None:
    """Fail loudly if a device-mode parameter tree carries a projection
    family this registry did not map onto containers — a tree that trains
    such a matrix digitally while claiming to be analog is the bug class
    this registry exists to retire."""
    bad = []

    def walk(p, path):
        if _is_container(p):
            return
        if isinstance(p, dict):
            for k, v in p.items():
                walk(v, path + (str(k),))
            return
        if getattr(p, "ndim", 0) < 2:
            return
        kind = classify_param(path)
        if kind in KINDS:
            bad.append("/".join(path))
        elif kind is None:
            bad.append("/".join(path) + " (unclassified)")

    walk(params, ())
    if bad:
        raise ValueError(
            "device-mode parameter tree has projection matrices that are "
            "not crossbar containers (they would train digitally while "
            f"claiming analog): {bad}")


def container_paths(params) -> Tuple[Tuple[str, ...], ...]:
    """Paths of every crossbar container in a parameter tree, sorted.

    The serve backend keys its per-container drift/read/pulse counters
    and recalibration sweep order on this enumeration; sorting makes the
    sweep order (and therefore the whole simulated maintenance schedule)
    deterministic.
    """
    out = []

    def walk(p, path):
        if _is_container(p):
            out.append(path)
            return
        if isinstance(p, dict):
            for k in p:
                walk(p[k], path + (str(k),))

    walk(params, ())
    return tuple(sorted(out))
