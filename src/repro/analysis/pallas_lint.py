"""Layer 2 — Pallas grid safety (RA2xx).

Every ``pallas_call`` in ``repro.kernels`` is captured at trace time (the
wrapper is invoked under ``jax.eval_shape`` with ``pl.pallas_call``
temporarily instrumented — no device execution, no kernel body runs) and
its BlockSpec index maps are then evaluated *concretely* over the full
grid.  That turns the grid bookkeeping — the part of a Pallas kernel that
fails silently — into proofs:

RA201  output coverage: collecting, for every output block, the ordered
       list of grid steps that map to it, the auditor requires (a) every
       block of ``out_shape`` is written (completeness) and (b) each
       block's grid steps are *consecutive* in the sequential grid order
       (race-freedom: an accumulator block may be revisited, but only
       while it is still resident — non-adjacent revisits mean two
       distant grid steps write the same window, the classic
       overlapping-out-spec bug).
RA202  every index-map result lands inside the operand's block grid.
RA203  padded operand shapes divide their block shapes (the wrapper's
       padding actually established the divisibility the grid assumes).
RA204  the per-(layer, tile) counter-PRNG seed blocks are pairwise
       unique: within each analog container across its full
       (L, tile_k, tile_n) grid, and across containers (distinct
       path-derived base seeds) — the shard-invariance precondition.

The capture helpers are public so the fixture tests can run the same
checks against deliberately broken BlockSpecs.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding

#: Grid-size guard: concrete evaluation caps out here (smoke geometries
#: are tiny; a full-scale config audit should shrink tiles, not enumerate
#: millions of grid points).
MAX_GRID_POINTS = 500_000


@dataclasses.dataclass
class SpecInfo:
    """One operand's BlockSpec, paired with its (padded) array shape."""
    block_shape: Tuple[Optional[int], ...]
    index_map: Callable[..., Any]
    shape: Tuple[int, ...]
    role: str  # "in[i]" or "out[i]"


@dataclasses.dataclass
class PallasCapture:
    """One traced ``pallas_call``: grid + every operand's spec/shape."""
    entry: str
    kernel_name: str
    grid: Tuple[int, ...]
    specs: List[SpecInfo]


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _spec_info(spec, shape, role: str) -> SpecInfo:
    """A BlockSpec with no block shape (a whole-array operand, e.g. the
    per-layer scalars in SMEM) is one block at the origin for every grid
    point."""
    shape = tuple(shape)
    if spec.block_shape is None:
        return SpecInfo(shape, lambda *_: (0,) * len(shape), shape, role)
    return SpecInfo(tuple(spec.block_shape), spec.index_map, shape, role)


def capture_pallas_calls(fn: Callable, *args, entry: str = "<fn>",
                         **kwargs) -> List[PallasCapture]:
    """Trace ``fn(*args)`` under ``eval_shape`` with ``pl.pallas_call``
    instrumented; returns one capture per pallas_call reached."""
    import jax
    from jax.experimental import pallas as pl

    captured: List[PallasCapture] = []
    real = pl.pallas_call

    def recorder(kernel, **kw):
        inner = real(kernel, **kw)

        def call(*operands):
            grid = kw.get("grid")
            grid = tuple(int(g) for g in (_as_list(grid) or []))
            specs: List[SpecInfo] = []
            in_specs = _as_list(kw.get("in_specs"))
            for i, (spec, op) in enumerate(zip(in_specs, operands)):
                specs.append(_spec_info(spec, op.shape, f"in[{i}]"))
            out_specs = _as_list(kw.get("out_specs"))
            out_shapes = _as_list(kw.get("out_shape"))
            for i, (spec, sh) in enumerate(zip(out_specs, out_shapes)):
                specs.append(_spec_info(spec, sh.shape, f"out[{i}]"))
            name = getattr(kernel, "func", kernel)  # partial -> func
            name = getattr(name, "__name__", str(name))
            captured.append(PallasCapture(entry, name, grid, specs))
            return inner(*operands)

        return call

    pl.pallas_call = recorder
    try:
        jax.eval_shape(fn, *args, **kwargs)
    finally:
        pl.pallas_call = real
    return captured


# --------------------------------------------------------------------------
# Checks over one capture
# --------------------------------------------------------------------------

def _eval_index_map(spec: SpecInfo, point: Tuple[int, ...]
                    ) -> Tuple[int, ...]:
    idx = spec.index_map(*point)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(v) for v in idx)


def check_capture(cap: PallasCapture) -> List[Finding]:
    """RA201/RA202/RA203 for one captured pallas_call."""
    findings: List[Finding] = []
    where = f"{cap.entry}:{cap.kernel_name}"
    n_points = int(np.prod(cap.grid)) if cap.grid else 1
    if n_points > MAX_GRID_POINTS:
        findings.append(Finding(
            "RA201", f"grid {cap.grid} exceeds {MAX_GRID_POINTS} points; "
            "audit with a smaller smoke geometry", entry=where))
        return findings

    # RA203 + per-operand block grids
    block_grids: List[Optional[Tuple[int, ...]]] = []
    for spec in cap.specs:
        dims = []
        ok = True
        for size, blk in zip(spec.shape, spec.block_shape):
            blk = 1 if blk is None else int(blk)
            if blk <= 0 or size % blk:
                findings.append(Finding(
                    "RA203", f"{spec.role} shape {spec.shape} not "
                    f"divisible by block {spec.block_shape} (wrapper "
                    "padding is wrong for this geometry)", entry=where))
                ok = False
                break
            dims.append(size // blk)
        block_grids.append(tuple(dims) if ok else None)

    # Sequential grid order: row-major, last grid dim fastest (matches the
    # TPU grid walk, which is what makes accumulator revisits legal).
    points = list(itertools.product(*(range(g) for g in cap.grid))) or [()]

    for spec, nblocks in zip(cap.specs, block_grids):
        if nblocks is None:
            continue
        writes: Dict[Tuple[int, ...], List[int]] = {}
        oob_reported = False
        for step, point in enumerate(points):
            idx = _eval_index_map(spec, point)
            if len(idx) != len(nblocks) or any(
                    v < 0 or v >= n for v, n in zip(idx, nblocks)):
                if not oob_reported:
                    findings.append(Finding(
                        "RA202", f"{spec.role} index map returns {idx} at "
                        f"grid point {point}, outside block grid "
                        f"{nblocks}", entry=where))
                    oob_reported = True
                continue
            if spec.role.startswith("out"):
                writes.setdefault(idx, []).append(step)
        if not spec.role.startswith("out") or oob_reported:
            continue
        # RA201: completeness + consecutive revisits
        expected = int(np.prod(nblocks))
        if len(writes) != expected:
            missing = expected - len(writes)
            findings.append(Finding(
                "RA201", f"{spec.role} coverage incomplete: {missing} of "
                f"{expected} output blocks never written", entry=where))
        for blk, steps in writes.items():
            if steps[-1] - steps[0] != len(steps) - 1:
                findings.append(Finding(
                    "RA201", f"{spec.role} block {blk} written at "
                    f"non-consecutive grid steps {steps[:4]}... — "
                    "write race / overlapping out spec", entry=where))
                break
    return findings


# --------------------------------------------------------------------------
# RA204 — seed-block uniqueness
# --------------------------------------------------------------------------

def check_seed_uniqueness(
        containers: Sequence[Tuple[str, Tuple[int, int, int], int]],
        entry: str = "seed-grid") -> List[Finding]:
    """``containers``: (path, (L_flat, tile_k, tile_n), base_seed) per
    analog container.  Checks that within each container the
    murmur-mixed per-(layer, tile) seeds are pairwise unique over the
    full grid, and that no two containers share a base seed stream."""
    findings: List[Finding] = []
    seen_bases: Dict[int, str] = {}
    for path, (lyr, tk, tn), base in containers:
        prev = seen_bases.get(base)
        if prev is not None:
            findings.append(Finding(
                "RA204", f"containers '{prev}' and '{path}' derive the "
                f"same base seed {base:#010x} — identical noise streams",
                entry=entry))
            continue
        seen_bases[base] = path
        li, ki, ni = np.meshgrid(np.arange(lyr, dtype=np.uint32),
                                 np.arange(tk, dtype=np.uint32),
                                 np.arange(tn, dtype=np.uint32),
                                 indexing="ij")
        seeds = _tile_seed_np(np.uint32(base), li, ki, ni).ravel()
        uniq = np.unique(seeds)
        if uniq.size != seeds.size:
            findings.append(Finding(
                "RA204", f"container '{path}' grid ({lyr},{tk},{tn}) has "
                f"{seeds.size - uniq.size} colliding (layer, tile) seed "
                "blocks", entry=entry))
    return findings


def _mix32_np(x: np.ndarray) -> np.ndarray:
    # numpy twin of kernels.xbar_update._mix32 (uint32 wrap-around).
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
        x = x ^ (x >> np.uint32(13))
        x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
        return x ^ (x >> np.uint32(16))


def _tile_seed_np(seed, layer, tile_k, tile_n) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = _mix32_np(np.uint32(seed) ^ np.uint32(0x9E3779B9))
        h = _mix32_np((h + np.uint32(0x9E3779B1) * layer).astype(np.uint32))
        h = _mix32_np((h + np.uint32(0x85EBCA77) * tile_k).astype(np.uint32))
        h = _mix32_np((h + np.uint32(0xC2B2AE3D) * tile_n).astype(np.uint32))
    return h


def _numpy_prng_matches_kernel() -> Optional[Finding]:
    """Guard: the numpy twin above must reproduce the kernel's _tile_seed
    bit-for-bit, else RA204's uniqueness proof is about the wrong hash."""
    import jax.numpy as jnp
    from repro.kernels.xbar_update import _tile_seed
    pts = [(0, 0, 0, 0), (1, 2, 3, 4), (0xDEADBEEF, 7, 31, 255)]
    for s, l, k, n in pts:
        ours = int(_tile_seed_np(np.uint32(s), np.uint32(l),
                                 np.uint32(k), np.uint32(n)))
        theirs = int(jnp.asarray(
            _tile_seed(jnp.uint32(s), l, k, n)))
        if ours != theirs:
            return Finding(
                "RA204", f"numpy seed twin diverges from kernel "
                f"_tile_seed at {(s, l, k, n)}: {ours:#x} != {theirs:#x}",
                entry="seed-twin")
    return None


# --------------------------------------------------------------------------
# The shipped-kernel audit
# --------------------------------------------------------------------------

def _kernel_entries() -> List[Tuple[str, Callable, tuple, dict]]:
    """(entry name, wrapper, ShapeDtypeStruct args, kwargs) for every
    shipped kernel wrapper, one per distinct spec layout."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from repro.core import AdcConfig, CrossbarConfig, TAOX
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.xbar_update import xbar_outer_update
    from repro.kernels.xbar_vmm import (fakequant_read_pallas,
                                        xbar_fused_read_inline)

    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    cfg = CrossbarConfig(rows=16, cols=16,
                         device=TAOX.replace(write_noise=0.5),
                         adc=AdcConfig(in_bits=4, out_bits=6))
    cfg0 = cfg.replace(device=cfg.device.replace(write_noise=0.0))
    # Fixed-range twin of the read config: the two range modes lower to
    # different epilogue code inside the fused kernel, so both index-map
    # layouts get audited.
    cfg_fix = cfg.replace(adc=AdcConfig(in_bits=4, out_bits=6,
                                        range_mode="fixed",
                                        sat_frac=0.03125))
    L, K, N, B = 3, 40, 24, 8
    g = S((L, K, N), f32)
    x = S((L, B, K), f32)
    d = S((L, B, N), f32)
    xc = S((L, B, K), jnp.bfloat16)
    dc = S((L, B, N), jnp.bfloat16)
    seed = S((), jnp.uint32)
    # Fused-read operands: K/N are deliberately ragged against the 16x16
    # tile (40 = 2.5 tiles, 24 = 1.5 tiles) so the wrapper's padding and
    # the grid's edge blocks are what RA201-RA203 actually see.  The
    # expert case (L, E, ...) exercises the lead-dim flattening the MoE
    # containers rely on.
    E = 2
    fused = partial(xbar_fused_read_inline, cfg=cfg, block_b=4,
                    impl="interpret")
    fused_t = partial(xbar_fused_read_inline, cfg=cfg, block_b=4,
                      transpose=True, impl="interpret")

    ent: List[Tuple[str, Callable, tuple]] = [
        ("xbar_outer_update[kernel-noise]",
         partial(xbar_outer_update, cfg=cfg, block_b=4,
                 noise_mode="kernel", impl="interpret"),
         (g, x, d, 1.0e-3), {"seed": seed}),
        ("xbar_outer_update[host-noise]",
         partial(xbar_outer_update, cfg=cfg, block_b=4,
                 noise_mode="host", impl="interpret"),
         (g, x, d, 1.0e-3), {"noise": g}),
        ("xbar_outer_update[no-noise]",
         partial(xbar_outer_update, cfg=cfg0, block_b=4,
                 noise_mode="none", impl="interpret"),
         (g, x, d, 1.0e-3), {}),
        # Pulse-train mode threads a second output block (the |x||d|
        # accumulator) through the same tile grid — its BlockSpecs and
        # epilogue indexing get their own audit rows.
        ("xbar_outer_update[pulse-train]",
         partial(xbar_outer_update, cfg=cfg, block_b=4,
                 noise_mode="kernel", impl="interpret",
                 update_mode="pulse_train"),
         (g, x, d, 1.0e-3), {"seed": seed}),
        ("xbar_outer_update[pulse-train-no-noise]",
         partial(xbar_outer_update, cfg=cfg0, block_b=4,
                 noise_mode="none", impl="interpret",
                 update_mode="pulse_train"),
         (g, x, d, 1.0e-3), {}),
        # The drives as the train step hands them over: the write
        # drivers' codes in bfloat16, which the kernel contracts in one
        # pass (two for pulse trains); their tape blocks are half the
        # bytes of the float32 ones.
        ("xbar_outer_update[codes]",
         partial(xbar_outer_update, cfg=cfg, block_b=4,
                 noise_mode="kernel", impl="interpret"),
         (g, xc, dc, 1.0e-3), {"seed": seed}),
        ("xbar_outer_update[codes-pulse-train]",
         partial(xbar_outer_update, cfg=cfg, block_b=4,
                 noise_mode="kernel", impl="interpret",
                 update_mode="pulse_train"),
         (g, xc, dc, 1.0e-3), {"seed": seed}),
        ("xbar_fused_read[vmm]",
         fused,
         (S((B, K), f32), S((K, N), f32), S((K, N), f32), 1.0), {}),
        ("xbar_fused_read[mvm]",
         fused_t,
         (S((B, N), f32), S((K, N), f32), S((K, N), f32), 1.0), {}),
        ("xbar_fused_read[vmm-batched]",
         fused,
         (x, g, g, 1.0), {}),
        ("xbar_fused_read[mvm-batched]",
         fused_t,
         (d, g, g, 1.0), {}),
        ("xbar_fused_read[vmm-expert]",
         fused,
         (S((L, E, B, K), f32), S((L, E, K, N), f32),
          S((L, E, K, N), f32), 1.0), {}),
        ("xbar_fused_read[mvm-expert]",
         fused_t,
         (S((L, E, B, N), f32), S((L, E, K, N), f32),
          S((L, E, K, N), f32), 1.0), {}),
        ("xbar_fused_read[vmm-fixed-range]",
         partial(xbar_fused_read_inline, cfg=cfg_fix, block_b=4,
                 impl="interpret"),
         (S((B, K), f32), S((K, N), f32), S((K, N), f32), 1.0), {}),
        ("fakequant_read[ragged-T]",
         partial(fakequant_read_pallas, adc=cfg.adc, rows=16, block_t=8,
                 interpret=True),
         (S((10, K), f32), S((K, N), f32)), {}),
        ("flash_attention[gqa-causal]",
         partial(flash_attention, causal=True, block_q=64, block_k=64,
                 interpret=True),
         (S((2, 128, 4, 32), f32), S((2, 128, 2, 32), f32),
          S((2, 128, 2, 32), f32)), {}),
        ("flash_attention[full]",
         partial(flash_attention, causal=False, block_q=64, block_k=64,
                 interpret=True),
         (S((1, 64, 2, 32), f32), S((1, 128, 2, 32), f32),
          S((1, 128, 2, 32), f32)), {}),
    ]
    return ent


def _config_seed_entries() -> Dict[
        str, List[Tuple[str, Tuple[int, int, int], int]]]:
    """Per shipped config: (path, (L_flat, tile_k, tile_n), base_seed)
    for every analog container, at the bench smoke geometry.  Grouped by
    config because only containers of ONE program share a seed space —
    the same path in two configs legitimately derives the same stream.

    The base seed mirrors the train step's derivation exactly
    (``_mix32(seed_base ^ crc32(path))`` with a representative
    ``seed_base`` of 0): two containers collide here iff their streams
    collide in :meth:`AnalogTrainStep._update_container`."""
    import zlib
    from functools import partial

    import jax

    from repro.configs.registry import ARCHS, get_config
    from repro.core.tiled_analog import is_analog_container
    from repro.models.model import init_params

    out: Dict[str, List[Tuple[str, Tuple[int, int, int], int]]] = {}
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True).replace(
            dtype="float32", analog=True, analog_mode="device",
            analog_rows=64, analog_cols=64)
        params = jax.eval_shape(partial(init_params, cfg=cfg),
                                jax.random.PRNGKey(0))
        rows = cols = 64

        def walk(p, path):
            if is_analog_container(p):
                shape = p["g"].shape
                lflat = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
                tk = -(-shape[-2] // rows)
                tn = -(-shape[-1] // cols)
                crc = zlib.crc32("/".join(path).encode()) & 0xFFFFFFFF
                base = int(_mix32_np(np.uint32(0) ^ np.uint32(crc)))
                out.setdefault(arch, []).append(
                    ("/".join(path), (lflat, tk, tn), base))
                return
            if isinstance(p, dict):
                for k2, v in sorted(p.items()):
                    walk(v, path + (k2,))

        walk(params, ())
    return out


def audit_pallas(root=None) -> List[Finding]:
    """Run the full Layer-2 audit on the shipped kernels + configs."""
    findings: List[Finding] = []
    for name, fn, args, kwargs in _kernel_entries():
        try:
            caps = capture_pallas_calls(fn, *args, entry=name, **kwargs)
        except Exception as e:  # trace failure is itself a finding
            findings.append(Finding(
                "RA202", f"tracing failed: {type(e).__name__}: {e}",
                entry=name))
            continue
        if not caps:
            findings.append(Finding(
                "RA202", "no pallas_call reached during trace "
                "(wrapper dispatched off the kernel path)", entry=name))
        for cap in caps:
            findings.extend(check_capture(cap))

    twin = _numpy_prng_matches_kernel()
    if twin is not None:
        findings.append(twin)
    else:
        for arch, entries in _config_seed_entries().items():
            findings.extend(check_seed_uniqueness(
                entries, entry=f"seed-grid[{arch}]"))
    return findings
