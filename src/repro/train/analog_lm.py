"""In-situ analog training for every model family (scaling the paper's
§VI MLP experiment to real workloads).

One ``AnalogTrainStep`` is the whole training rule, jitted and donated so
it compiles exactly once and updates conductances in place:

  1. the parameter tree is *split* (``core.tiled_analog.split_tapes``):
     digital leaves plus per-container tape slots form the differentiated
     tree, while every container's g/ref/w_scale is hoisted into frozen
     (closure) position — the backward pass deposits the quantised
     write-driver operands (x_q, d_q) in the tape cotangents and no dense
     (K, N) weight gradient, not even a zeros fill, is ever formed,
  2. forward = VMM, backward = MVM through the same conductances
     (``models/layers.project`` dispatches on the container),
  3. every container's update is the paper's rank-k parallel write: the
     tapes go straight into the *layer-batched* fused kernel
     ``kernels/xbar_update.xbar_outer_update`` — one sweep over a
     scan-stacked (L, K, N) container (outer product + nonlinear /
     asymmetric / stochastic device model, one HBM round-trip per tile),
     with write noise generated in-kernel from one scalar seed per
     container (``noise_mode="kernel"``; the legacy pre-generated field
     path stays behind ``noise_mode="host"``),
  4. digital leaves (embeddings, norms, routers, the logits head) take
     plain SGD — the paper keeps exactly these on the digital core.

The mapping from parameter path to container / tape route / update view
is the family-agnostic registry (``core/analog_registry.py``): MoE
expert stacks are expert-batched (L, E, K, N) containers whose expert
dim flattens onto the kernel's layer grid (one ``pallas_call`` per
container; dropless per-expert tapes, the routed row counts beside
them), SSD in/out projections are ordinary scan-stacked containers, the
hybrid shared block tapes one operand slot per group application, and
the fused cross-attention array is driven by both token streams in one
application.  The first call
audits the tree — an unmapped projection-family matrix raises instead
of silently training digitally.

The step also carries a hardware cost roll-up: layer shapes joined with
``hwmodel/arch_cost`` project the energy/latency of each step on the
analog accelerator vs digital-ReRAM vs SRAM cores (``step.cost``).

Multi-device sharding
---------------------
Pass ``mesh=`` to run the step sharded (docs/analog_pipeline.md
§Sharding).  The parallel axis is the container *tile grid*, not the
batch: conductances/reference arrays shard at whole-tile granularity —
column-tiles over ``model``, row-tiles over the FSDP axes, flipped for
row-parallel consumers (``launch/sharding.analog_container_pspec``).
The whole step body runs under ``shard_map``: the read is shard-local
(each shard drives only the tile blocks it owns and exchanges ordered
per-tile ADC partial sums — ``kernels/xbar_vmm.manual_collective_read``;
conductances never cross a shard boundary), the expert dim of an MoE
container is an EP dispatch (each shard reads only its own experts'
rows of the replicated dispatch buffer and the combine gathers the
small output buffers), and the rank-k write updates only the local tile
block with shard-invariant counter-PRNG seeds.  Activations stay
replicated, and every cross-shard exchange is an arithmetic-free gather
in pinned order (``core/shardctx.py`` spells out the determinism
contract), so a 1-device and an N-device run of the same seed produce
*bit-identical* conductances (tests/test_sharded_analog.py) while the
per-step collective bytes scale with activations instead of parameters.
Use :meth:`shard_state` to lay an initial state out on the mesh.
"""
from __future__ import annotations

import contextlib
import zlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.base import (AnalogMode, ModelConfig,
                                resolve_analog_mode)
from repro.core import analog_registry as registry
from repro.core import shardctx
from repro.core.adc import adc_quantize
from repro.core.periodic_carry import carry_fold
from repro.core.tiled_analog import (crossbar_from_model,
                                     is_analog_container, merge_tapes,
                                     split_tapes)
from repro.hwmodel.arch_cost import train_step_cost
from repro.kernels.xbar_update import (_flat_axis_index, _mix32,
                                       _wrap_shard_map,
                                       xbar_outer_update_inline,
                                       xbar_sharded_update)
from repro.models import model as M

Array = jax.Array


def _spec_names(entry) -> tuple:
    """PartitionSpec entry -> tuple of mesh axis names (() if None)."""
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _gather_dim(x: Array, names, axis: int) -> Array:
    """all_gather one sharded dim back to full size (inside shard_map).
    Minor axis first so a dim sharded over ("pod", "data") reassembles
    pod-major, matching the at-rest layout.  Arithmetic-free — exact."""
    for a in reversed(names):
        x = jax.lax.all_gather(x, a, axis=axis, tiled=True)
    return x


def init_state(key: Array, cfg: ModelConfig) -> dict:
    return {"params": M.init_params(key, cfg),
            "step": jnp.zeros((), jnp.int32)}


def _path_key(key: Array, path: Tuple[str, ...]) -> Array:
    """Stable (process-independent) per-container PRNG stream."""
    return jax.random.fold_in(
        key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)


class AnalogTrainStep:
    """Jitted, donated-buffer analog-SGD step: ``state, metrics = step(state,
    batch, key)``.  ``step.compiles`` counts tracings (must stay at 1);
    ``step.cost`` is the projected per-step hardware cost (available after
    the first call, when the token count is known).

    ``impl`` selects the update-kernel execution path ("pallas" |
    "interpret" | "fused" | None = auto: Mosaic on TPU, the fused jnp twin
    elsewhere); ``noise_mode`` selects in-kernel counter-PRNG write noise
    ("kernel", the default) or the legacy host-generated field ("host").
    ``read_impl`` selects the forward/backward *read* path the same way
    (``cfg.analog_read_impl`` / kernels/xbar_vmm.READ_IMPLS; "auto" =
    the fused jnp twin on CPU, the fused DAC→MXU→ADC kernel on TPU).

    ``mesh`` (optional) runs the step sharded over a device mesh with
    ``data``/``model`` axes: containers split at tile granularity, the
    whole step runs under shard_map with shard-local reads and writes
    (``read_mode="local"``; ``"gather"`` keeps the legacy
    gather-then-replay read), and the result is bit-identical to the
    single-device step for the same seed (see the module docstring).
    The state should be laid out with :meth:`shard_state` first; the batch
    and key are replicated automatically.
    """

    def __init__(self, cfg: ModelConfig, lr: float,
                 interpret: Optional[bool] = None, bits: int = 8,
                 impl: Optional[str] = None, noise_mode: str = "kernel",
                 mesh=None, exact: bool = True,
                 read_impl: Optional[str] = None,
                 read_mode: str = "local"):
        if read_impl is not None:
            # Forward/backward read path (kernels/xbar_vmm.READ_IMPLS);
            # rides the config so every jitted consumer routes through it.
            cfg = cfg.replace(analog_read_impl=read_impl)
        if resolve_analog_mode(cfg) is not AnalogMode.DEVICE:
            raise ValueError(
                f"AnalogTrainStep needs a device-mode config "
                f"(resolved {resolve_analog_mode(cfg).value!r}); set "
                f"analog=True, analog_mode={AnalogMode.DEVICE.value!r}")
        if noise_mode not in ("kernel", "host"):
            raise ValueError("noise_mode must be 'kernel' or 'host'")
        if read_mode not in ("local", "gather"):
            raise ValueError("read_mode must be 'local' or 'gather'")
        self.cfg = cfg
        self.lr = lr
        self.bits = bits
        self.xcfg = crossbar_from_model(cfg)
        if impl is None and interpret is not None:
            impl = "interpret" if interpret else "pallas"
        self.impl = impl or "auto"
        self.noise_mode = noise_mode
        self.mesh = mesh
        self.exact = exact
        # Exact-mode read dataflow: "local" (default) is the
        # manual-collective shard-local read — conductances never move,
        # the shards exchange only ordered partial-sum accumulators;
        # "gather" is the legacy gather-then-replay path, kept as the A/B
        # reference for parity tests and collective-byte accounting.
        self.read_mode = read_mode
        self.cost: Optional[dict] = None
        # With a mesh the jit carries explicit in/out shardings (built at
        # first call, when the state structure is known) so the parameter
        # layout is pinned across steps — GSPMD would otherwise be free to
        # re-lay out e.g. the embedding on step 2, retracing the step and
        # resharding the logits contraction mid-run.
        self._step = None if mesh is not None \
            else jax.jit(self._step_impl, donate_argnums=(0,))

    # ------------------------------------------------------------------ api

    def __call__(self, state: dict, batch: Dict[str, Array], key: Array
                 ) -> Tuple[dict, Dict[str, Array]]:
        if self.cost is None:
            # First call: audit the tree — every projection-family matrix
            # must be a crossbar container (core/analog_registry); a tree
            # that would train one digitally while claiming analog fails
            # here, loudly, before any step runs.
            registry.validate_device_params(state["params"], self.cfg)
            self.cost = train_step_cost(
                self.cfg, n_tokens=int(batch["tokens"].size),
                bits=self.bits, ctx_len=batch["tokens"].shape[-1],
                n_shards=self.mesh.size if self.mesh is not None else 1)
        if self.mesh is None:
            return self._step(state, batch, key)
        if self._step is None:
            self._build_sharded_step(state, batch)
        if not self.exact:
            # The TP read path relies on the shard context: the crossbar
            # sim pins its cross-tile accumulations and read outputs at
            # trace time (core/shardctx.replicate_for_exact_reduce).
            prev = shardctx.get_shard_context()
            shardctx.set_shard_context(self.mesh, None)
            try:
                return self._step(state, batch, key)
            finally:
                shardctx.set_shard_context(*prev)
        return self._step(state, batch, key)

    def _build_sharded_step(self, state, batch):
        """First call with a mesh: pin the jit's in/out shardings (so the
        parameter layout is stable across steps — GSPMD would otherwise be
        free to re-lay out e.g. the embedding on step 2 and retrace), and
        in exact mode wrap the whole step body in shard_map."""
        from jax.sharding import PartitionSpec as P
        repl = self._replicated()
        state_sh = self.state_shardings(state)
        if self.exact:
            # Record each container's partition specs + global shape; the
            # shard_map body sees only local tile blocks.
            self._cspecs = {}
            self._collect_cspecs(state["params"], ())
            state_spec = jax.tree.map(lambda s: s.spec, state_sh)
            batch_spec = jax.tree.map(lambda _: P(), batch)
            fn = _wrap_shard_map(self._step_impl, self.mesh,
                                 (state_spec, batch_spec, P()),
                                 (state_spec, P()))
        else:
            fn = self._step_impl
        # ``repl`` is a pytree *prefix* covering the batch / metrics dicts.
        self._step = jax.jit(fn, donate_argnums=(0,),
                             in_shardings=(state_sh, repl, repl),
                             out_shardings=(state_sh, repl))

    def _collect_cspecs(self, p, path):
        from repro.launch.sharding import analog_update_specs
        if is_analog_container(p):
            # p["g"] may be laid out sharded already; .shape is global.
            self._cspecs[path] = (
                analog_update_specs(path, p["g"].shape, self.cfg,
                                    self.mesh),
                tuple(p["g"].shape))
            return
        if isinstance(p, dict):
            for k, v in p.items():
                self._collect_cspecs(v, path + (k,))

    @property
    def compiles(self) -> Optional[int]:
        if self._step is None:
            return 0
        size = getattr(self._step, "_cache_size", None)
        return size() if size is not None else None

    # ------------------------------------------------------- mesh layout

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def state_shardings(self, state: dict):
        """NamedShardings for a train state on this step's mesh: analog
        containers tile-sharded per the policy, everything else (digital
        leaves, the step counter) replicated."""
        from repro.launch import sharding as S
        return {
            "params": S.analog_params_shardings(state["params"], self.cfg,
                                                self.mesh),
            "step": self._replicated(),
        }

    def shard_state(self, state: dict) -> dict:
        """Lay an (unsharded) train state out on the mesh.  Containers
        split at tile granularity; shapes that don't divide degrade to
        replication exactly like the digital policy."""
        if self.mesh is None:
            return state
        return jax.device_put(state, self.state_shardings(state))

    # ------------------------------------------------------------- internals

    def _step_impl(self, state, batch, key):
        cfg = self.cfg
        params = state["params"]
        n_tokens = batch["tokens"].size  # static under jit

        # Sharded + exact (the default contract): this body runs INSIDE
        # shard_map — each device holds its local tile blocks of every
        # container.  read_mode="local" (default) annotates each container
        # with a static ShardMeta and the read itself goes shard-local
        # (kernels/xbar_vmm.manual_collective_read): every shard runs the
        # fused tile pipeline on only the blocks it owns and the shards
        # exchange ordered per-tile ADC partial sums — never conductances
        # — so per-step collective bytes scale with activations instead
        # of parameters.  Bit-identity to the 1-device step holds because
        # every cross-shard float reduction is an ordered gather + a
        # single full-axis reduce in single-device order, and every
        # tile-local stage sees exactly the single-device operands (the
        # per-stage argument lives on manual_collective_read's docstring).
        # read_mode="gather" keeps the legacy gather-then-replay path:
        # all-gather every container, replay the single-device program,
        # write the local block (bit-identity by structural identity, at
        # parameter-sized collective cost).  ``exact=False`` skips the
        # shard_map wrapper and keeps the containers sharded through a
        # GSPMD read path instead: true tensor-parallel VMM/MVM
        # (activations pinned replicated at every container boundary,
        # cross-tile ADC sums pinned to global order — core/xbar_ops) at
        # the cost of ulp-level drift.  The rank-k write below always
        # updates only the local tile block (tapes sliced, PRNG counters
        # globally offset).
        read_params = params
        if self.mesh is not None and self.exact:
            if self.read_mode == "local":
                read_params = self._annotate_containers(params, ())
            else:
                read_params = self._gather_containers(params, ())

        # Hoist g/ref/w_scale out of the differentiated arguments: the grads
        # tree holds exactly the tape cotangents + digital gradients.  The
        # registry resolves each container's tape route: a dropless slot
        # per token and expert (with the routed row counts beside them),
        # one slot block per application for the hybrid shared weights,
        # n_tokens rows everywhere else.
        diff, frozen = split_tapes(
            read_params, n_tokens,
            tokens_for=lambda path, shape: registry.tape_lead(
                path, cfg, n_tokens, batch["tokens"].shape),
            counted=lambda path: registry.classify(path)
            == registry.EXPERT_BATCHED)
        (loss, metrics), grads = jax.value_and_grad(
            lambda d: M.loss_fn(merge_tapes(d, frozen), batch, cfg),
            has_aux=True)(diff)
        rail = []
        # One threefry draw per step; per-container seeds come out of the
        # same counter mix the kernel PRNG uses (keyed on the tree path).
        seed_base = jax.random.bits(key, (), jnp.uint32) \
            if self.xcfg.device.write_noise > 0.0 \
            and self.noise_mode == "kernel" else None
        new_params = self._update(params, grads, key, seed_base, (), rail)
        if self.xcfg.carry and getattr(cfg, "carry_period", 0) > 0:
            # Periodic carry (paper §VI.B): every carry_period steps a
            # serial closed-loop pass folds each container's carry (LSB)
            # array into its primary one significance level up.  The cond
            # lives INSIDE the jitted, donated step — compiles stays at 1
            # and the sweep is elementwise on the local tile blocks, so it
            # is shard-local under shard_map (no new collectives) and the
            # sharded==unsharded bit-parity contract extends over it.
            with jax.named_scope("xbar.carry"):
                new_params = jax.lax.cond(
                    (state["step"] + 1) % int(cfg.carry_period) == 0,
                    self._carry_sweep, lambda t: t, new_params)
        if not rail:
            # Every family maps through the registry now; an empty rail
            # means the tree genuinely carries no containers (a digital
            # tree passed to the analog step) — fail loudly.
            raise ValueError(
                f"no analog containers in params for family "
                f"{cfg.family!r}; was the state built with "
                f"analog_mode='device'?")
        out = {"loss": loss, **metrics}
        # fraction of devices pinned at the conductance rails — the
        # leading indicator of window exhaustion (paper §V.A).
        out["g_rail_frac"] = sum(rail) / len(rail)
        return {"params": new_params, "step": state["step"] + 1}, out

    def _annotate_containers(self, p, path):
        """Attach a static ``shardctx.ShardMeta`` to each tile-sharded
        container (read_mode="local").  The meta rides the ``"tp_meta"``
        key — hashable treedef metadata, so it survives the loss scan's
        xs slicing and keys the custom-VJP nondiff cache — and routes
        ``core.tiled_analog`` to the manual-collective shard-local read.
        Containers the policy left fully replicated are returned
        untouched and read exactly as on one device."""
        if is_analog_container(p):
            specs, gshape = self._cspecs[path]
            g_spec = specs["g"]
            lead = tuple(_spec_names(e) for e in g_spec[:-2])
            row = _spec_names(g_spec[-2])
            col = _spec_names(g_spec[-1])
            if not (row or col or any(lead)):
                return p
            sizes = tuple((a, int(self.mesh.shape[a]))
                          for a in self.mesh.axis_names)
            meta = shardctx.ShardMeta(shape=gshape, row=row, col=col,
                                      lead=lead, axis_sizes=sizes)
            return {**p, "tp_meta": meta}
        if isinstance(p, dict):
            return {k: self._annotate_containers(v, path + (k,))
                    for k, v in p.items()}
        return p

    def _gather_containers(self, p, path):
        """Reassemble full conductance/reference/scale arrays from local
        tile blocks for the read path (inside shard_map) — the legacy
        ``read_mode="gather"`` dataflow, kept as the A/B reference for
        the manual-collective read.  all_gather moves bits, never adds
        floats — the gathered array is exactly the single-device array."""
        if is_analog_container(p):
            specs = self._cspecs[path][0]
            out = dict(p)
            leaves = [("g", "g"), ("ref", "g"), ("w_scale", "w_scale")]
            if "g_carry" in p:
                leaves.append(("g_carry", "g"))  # sharded identically to g
            for leaf, spec_key in leaves:
                x = p[leaf]
                for d, entry in enumerate(specs[spec_key]):
                    names = _spec_names(entry)
                    if names:
                        x = _gather_dim(x, names, d)
                out[leaf] = x
            return out
        if isinstance(p, dict):
            return {k: self._gather_containers(v, path + (k,))
                    for k, v in p.items()}
        return p

    def _update(self, p, g, key, seed_base, path, rail):
        if is_analog_container(p):
            # An expert stack's write is named with its reads
            # (``moe.experts``, models/moe).
            expert = registry.classify(path) == registry.EXPERT_BATCHED
            outer = jax.named_scope("moe.experts") if expert \
                else contextlib.nullcontext()
            with outer, jax.named_scope("xbar.write"):
                return self._update_container(p, g, key, seed_base, path,
                                              rail)
        if isinstance(p, dict):
            return {k: self._update(p[k], g[k], key, seed_base,
                                    path + (k,), rail)
                    for k in p}
        return p - self.lr * g.astype(p.dtype)

    def _update_container(self, p, tapes, key, seed_base, path, rail):
        """The paper's Fig. 3c parallel write, fused on the (L, tiles)
        grid: one kernel sweep per container.  The registry flattens the
        container's lead dims — scan layers, the expert dim of an
        expert-batched stack (hoisted outermost so an EP shard is a
        contiguous flattened range), the per-application tape dim of the
        hybrid shared block (collapsed into the token contraction) — onto
        the kernel's layer axis, so the write stays ONE ``pallas_call``
        per container for every family.  On a mesh each shard writes only
        the tiles it owns (tape slices local, PRNG counters globally
        indexed).

        An expert-batched stack's tapes carry each expert's routed row
        counts (``n_tape``), which the kernel uses to skip the rest; its
        experts draw the write noise of experts ``first_expert ..`` of
        the whole layer: the flattened layer index is offset by
        ``first_expert`` experts' worth of rows."""
        smap = self.mesh is not None and self.exact
        kind = registry.classify(path)
        rows = tapes.get("n_tape")
        held_off = 0
        if kind == registry.EXPERT_BATCHED and p["g"].ndim > 2:
            held_off = self.cfg.first_expert \
                * int(np.prod(p["g"].shape[:-3]))
        noise = seed = None
        mode = "none"
        if seed_base is not None:
            mode = "kernel"
            seed = _mix32(seed_base ^ jnp.uint32(
                zlib.crc32("/".join(path).encode())))
        elif self.xcfg.device.write_noise > 0.0:
            mode = "host"
            shape = self._cspecs[path][1] if smap else p["g"].shape
            noise = jax.random.normal(_path_key(key, path), shape,
                                      dtype=jnp.float32)
        scale = jnp.asarray(-self.lr, jnp.float32) \
            * jnp.asarray(p["w_scale"], jnp.float32)
        # Periodic carry: every training write lands on the carry (LSB)
        # array, one significance level below the primary — a requested
        # Δw_eff needs a base× larger conductance move there (the
        # effective read divides by carry_base), which keeps the carry
        # cell swinging through the middle of its window where the device
        # is most linear and shrinks the *effective* write noise by
        # ~sqrt(base).  The primary only ever moves in closed-loop carry
        # sweeps (paper §VI.B, _carry_sweep).
        leaf = "g_carry" if "g_carry" in p else "g"
        if leaf == "g_carry":
            scale = scale * jnp.float32(self.xcfg.carry_base)
        if smap:
            g_new, railed, total = self._local_block_update(
                p[leaf], tapes, scale, noise, seed, mode, path, kind,
                held_off)
            rail.append(railed / total)
        else:
            g3, x3, d3, s1, n3, unflatten = registry.flatten_lead(
                kind, p[leaf], tapes["x_tape"], tapes["d_tape"], scale,
                noise)
            if self.mesh is not None:  # GSPMD TP path: nested shard_map
                specs = self._flat_update_specs(path, p["g"].shape, kind)
                g3_new = xbar_sharded_update(
                    g3, x3, d3, s1, self.xcfg, self.mesh, specs,
                    noise=n3, seed=seed, noise_mode=mode, impl=self.impl)
            else:
                g3_new = xbar_outer_update_inline(
                    g3, x3, d3, s1, self.xcfg, noise=n3, seed=seed,
                    noise_mode=mode, impl=self.impl,
                    tile_offsets=(held_off, 0, 0),
                    rows=None if rows is None
                    else registry.flatten_counts(kind, rows))
            g_new = unflatten(g3_new)
            dev = self.xcfg.device
            span = dev.gmax - dev.gmin
            # sums of 0/1 floats are order-exact, so this mean matches the
            # single-device value bit for bit even over a sharded array
            rail.append(jnp.mean(
                (g_new <= dev.gmin + 1e-3 * span)
                | (g_new >= dev.gmax - 1e-3 * span)).astype(jnp.float32))
        return {**p, leaf: g_new}

    def _carry_readout(self, v):
        """Serial readout of a carry cell's signed value through the ADC
        transfer — the elementwise twin of driving the fused read kernel
        with unit rows (tests/test_periodic_carry_container.py pins the
        equivalence against ``xbar_fused_read_inline``)."""
        return adc_quantize(v, self.xcfg.w_swing, self.xcfg.adc)

    def _carry_sweep(self, p):
        """One serial carry pass (paper §VI.B / ref [35]): read each
        carry cell through the ADC, fold the transferable amount into the
        primary array one significance level up (closed-loop writes are
        exact), and leave the untransferable residual — clamp leftovers
        plus sub-LSB mass — in the carry cell, where the effective read
        still sees it.  Elementwise, so it runs unchanged on local tile
        blocks inside shard_map and on GSPMD-sharded full arrays."""
        if is_analog_container(p):
            if "g_carry" not in p:
                return p
            cfg = self.xcfg
            dev = cfg.device
            t, inc = carry_fold(p["g_carry"], p["g"], p["ref"],
                                cfg.carry_base, cfg,
                                quantize=self._carry_readout)
            g = jnp.minimum(jnp.maximum(p["g"] + inc, dev.gmin), dev.gmax)
            gc = jnp.minimum(jnp.maximum(p["g_carry"] - t, dev.gmin),
                             dev.gmax)
            return {**p, "g": g, "g_carry": gc}
        if isinstance(p, dict):
            return {k: self._carry_sweep(v) for k, v in p.items()}
        return p

    def _flat_update_specs(self, path, g_shape, kind):
        """Partition specs for the *flattened* (Lflat, K, N) update view
        of a container on the GSPMD path: the flattened lead dim carries
        the expert axis names (layer entries are never sharded, and the
        hoist makes an EP shard a contiguous block of flattened rows)."""
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import analog_update_specs
        specs = analog_update_specs(path, g_shape, self.cfg, self.mesh)
        lead = len(g_shape) - 2
        if lead == 0:
            return specs
        lead_entries = [e for e in specs["g"][:lead] if e is not None]
        lead0 = lead_entries[0] if lead_entries else None
        return {
            "g": P(lead0, specs["g"][-2], specs["g"][-1]),
            "x_tape": P(lead0, None, specs["x_tape"][-1]),
            "d_tape": P(lead0, None, specs["d_tape"][-1]),
            "scale": P(lead0),
        }

    def _local_block_update(self, g_arr, tapes, scale, noise, seed, mode,
                            path, kind, held_off=0):
        """Rank-k write of one shard's tile block (inside shard_map):
        slice the (replicated) tapes and noise to the block this shard
        owns — including its expert range for expert-batched containers —
        offset the counter-PRNG by the block's global base (layer, tile)
        coordinates, flatten the lead dims, and run the plain
        layer-batched kernel on the local conductances.  Returns
        (g_new, railed_count, total_cells) with the count psum'd over the
        sharded axes — 0/1 sums are order-exact, so the rail fraction
        matches the single-device metric bitwise."""
        specs, gshape = self._cspecs[path]
        mesh = self.mesh
        rows, cols = self.xcfg.rows, self.xcfg.cols
        g_spec = specs["g"]
        lead = len(gshape) - 2
        names_r = _spec_names(g_spec[-2])
        names_c = _spec_names(g_spec[-1])
        g_loc = g_arr  # the primary or, under periodic carry, the carry LSB
        k_loc, n_loc = g_loc.shape[-2:]

        def slice_dim(x, names, size_loc, axis):
            if not names:
                return x
            start = (_flat_axis_index(mesh, names)
                     * jnp.uint32(size_loc)).astype(jnp.int32)
            return jax.lax.dynamic_slice_in_dim(x, start, size_loc,
                                                axis=axis)

        x_loc = slice_dim(tapes["x_tape"], names_r, k_loc, -1)
        d_loc = slice_dim(tapes["d_tape"], names_c, n_loc, -1)
        if noise is not None:
            noise = slice_dim(noise, names_r, k_loc, lead)
            noise = slice_dim(noise, names_c, n_loc, lead + 1)
        # Sharded lead dims (the expert axis of an expert-batched
        # container): slice the replicated tapes/noise to the expert range
        # this shard owns, and offset the flattened layer index of the
        # counter PRNG by the range's global base.  The registry hoists
        # the (single) sharded lead dim outermost, so the offset is one
        # scalar: base_expert * (flattened rows per expert).
        lead_off = jnp.uint32(held_off)
        for d in range(lead):
            names_d = _spec_names(g_spec[d])
            if not names_d:
                continue
            size_d = g_loc.shape[d]
            x_loc = slice_dim(x_loc, names_d, size_d, d)
            d_loc = slice_dim(d_loc, names_d, size_d, d)
            if noise is not None:
                noise = slice_dim(noise, names_d, size_d, d)
            assert registry.hoist_axis(kind, len(gshape)) in (d, None), (
                "sharded lead dim must be the registry's hoisted axis")
            rest = int(np.prod([g_loc.shape[i] for i in range(lead)
                                if i != d])) if lead > 1 else 1
            lead_off = lead_off + _flat_axis_index(mesh, names_d) \
                * jnp.uint32(size_d * rest)
        g3, x3, d3, s1, n3, unflatten = registry.flatten_lead(
            kind, g_loc, x_loc, d_loc, scale, noise)
        offs = (lead_off,
                _flat_axis_index(mesh, names_r) * jnp.uint32(k_loc // rows)
                if names_r else 0,
                _flat_axis_index(mesh, names_c) * jnp.uint32(n_loc // cols)
                if names_c else 0)
        g3_new = xbar_outer_update_inline(
            g3, x3, d3, s1, self.xcfg, noise=n3, seed=seed,
            noise_mode=mode, impl=self.impl, tile_offsets=offs)
        g_new = unflatten(g3_new)
        dev = self.xcfg.device
        span = dev.gmax - dev.gmin
        railed = jnp.sum(((g_new <= dev.gmin + 1e-3 * span)
                          | (g_new >= dev.gmax - 1e-3 * span))
                         .astype(jnp.float32))
        used = tuple(a for e in g_spec for a in _spec_names(e))
        if used:
            # audit: allow RA103 -- metric-only psum of 0/1 counts: integer sums are order-exact, bit-identity unaffected
            railed = jax.lax.psum(railed, used)
        return g_new, railed, float(np.prod(gshape))


def make_analog_sgd_step(cfg: ModelConfig, lr: float,
                         interpret: Optional[bool] = None,
                         bits: int = 8, impl: Optional[str] = None,
                         noise_mode: str = "kernel",
                         mesh=None, exact: bool = True,
                         read_impl: Optional[str] = None,
                         read_mode: str = "local"
                         ) -> AnalogTrainStep:
    """The analog-SGD training step for a device-mode transformer config.

    ``mesh``: optional jax mesh with ``data``/``model`` axes — runs the
    step sharded over the container tile grid (bit-identical to the
    single-device step when ``exact=True``, the default; see
    :class:`AnalogTrainStep`).  ``read_impl`` overrides the forward /
    backward read execution path (``cfg.analog_read_impl``);
    ``read_mode`` selects the exact-mode read dataflow ("local" =
    manual-collective shard-local read, "gather" = legacy
    gather-then-replay)."""
    return AnalogTrainStep(cfg, lr, interpret=interpret, bits=bits,
                           impl=impl, noise_mode=noise_mode, mesh=mesh,
                           exact=exact, read_impl=read_impl,
                           read_mode=read_mode)
