"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds abstract inputs (ShapeDtypeStructs — no
allocation), jits the appropriate step (train_step / prefill / decode_step)
with the launch/sharding.py policy, compiles for the production mesh, and
records:

  * memory_analysis()      — proves the cell fits per-device HBM,
  * cost_analysis()        — XLA's own FLOP/byte counts (loop bodies x1),
  * hlo_analysis.analyze() — loop-corrected per-device FLOPs, memory
    traffic and collective link-bytes for EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax

from repro.configs import (ASSIGNED, SHAPE_BY_NAME, applicable_shapes,
                           get_config)
from repro.launch import sharding
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.train import train_loop
from repro.train.optimizer import adamw


def _cell_fns(cfg, shape):
    """(fn, abstract_args, in_shardings builder) for one cell."""
    opt = adamw(3e-4)

    if shape.kind == "train":
        step = train_loop.make_train_step(cfg, opt)

        def build(mesh):
            state = train_loop.abstract_state(cfg, opt)
            batch = M.input_specs(cfg, shape)
            p_sh = sharding.params_shardings(state["params"], cfg, mesh)
            opt_sh = {
                "m": sharding.params_shardings(state["opt"]["m"], cfg,
                                               mesh),
                "v": sharding.params_shardings(state["opt"]["v"], cfg,
                                               mesh),
                "t": sharding.replicated(mesh),
            }
            state_sh = {"params": p_sh, "opt": opt_sh,
                        "step": sharding.replicated(mesh), "err_fb": ()}
            b_sh = sharding.batch_shardings(batch, mesh)
            return (state, batch), (state_sh, b_sh), (state_sh, None)
        return step, build

    if shape.kind == "prefill":
        def fn(params, batch):
            return M.prefill(params, batch, cfg, max_len=shape.seq_len)

        def build(mesh):
            params = jax.eval_shape(
                lambda: M.init_params(jax.random.PRNGKey(0), cfg))
            batch = M.input_specs(cfg, shape)
            p_sh = sharding.params_shardings(params, cfg, mesh)
            b_sh = sharding.batch_shardings(batch, mesh)
            cache = M.cache_specs(cfg, shape.global_batch, shape.seq_len)
            c_sh = sharding.cache_shardings(cache, cfg, mesh)
            return (params, batch), (p_sh, b_sh), (None, c_sh)
        return fn, build

    # decode: one token against a seq_len cache
    def fn(params, cache, batch):
        extras = {k: v for k, v in batch.items() if k != "tokens"}
        return M.decode_step(params, cache, batch["tokens"], cfg,
                             batch_extras=extras or None)

    def build(mesh):
        params = jax.eval_shape(
            lambda: M.init_params(jax.random.PRNGKey(0), cfg))
        batch = M.input_specs(cfg, shape)
        cache = M.cache_specs(cfg, shape.global_batch, shape.seq_len)
        p_sh = sharding.params_shardings(params, cfg, mesh)
        b_sh = sharding.batch_shardings(batch, mesh)
        c_sh = sharding.cache_shardings(cache, cfg, mesh)
        return (params, cache, batch), (p_sh, c_sh, b_sh), \
            (None, c_sh)
    return fn, build


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             smoke: bool = False) -> dict:
    t0 = time.time()
    cfg = get_config(arch, smoke=smoke)
    if os.environ.get("REPRO_SSM_CHUNK"):  # K7 (perf): SSD chunk length
        cfg = cfg.replace(ssm_chunk=int(os.environ["REPRO_SSM_CHUNK"]))
    if os.environ.get("REPRO_ANALOG"):  # analog-crossbar projection mode
        cfg = cfg.replace(analog=True)
    shape = SHAPE_BY_NAME[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "ok": False}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.devices.size
        from repro.launch.mesh import dp_axes
        from repro.models.layers import set_shard_context
        set_shard_context(mesh, dp_axes(mesh))
        fn, build = _cell_fns(cfg, shape)
        args, in_sh, out_sh = build(mesh)
        with mesh:
            # audit: allow RA304 -- lower/compile probe only, never executed
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        hlo = analyze(hlo_text, default_group=n_dev)
        if os.environ.get("DRYRUN_SAVE_HLO"):
            import zstandard
            hdir = Path(os.environ.get("DRYRUN_HLO_DIR", "results/hlo"))
            hdir.mkdir(parents=True, exist_ok=True)
            tag = (f"{arch}__{shape_name}__"
                   f"{'multi' if multi_pod else 'single'}")
            (hdir / f"{tag}.hlo.zst").write_bytes(
                zstandard.compress(hlo_text.encode()))
        rec.update({
            "ok": True,
            "devices": int(n_dev),
            "lower_s": round(t_lower - t0, 1),
            "compile_s": round(t_compile - t_lower, 1),
            "mem": {
                # argument/output sizes are reported per device; temp is the
                # host-total across all addressable devices (empirically
                # verified) — divide by the device count for per-device.
                "argument_gb": mem.argument_size_in_bytes / 1e9,
                "output_gb": mem.output_size_in_bytes / 1e9,
                "temp_gb": mem.temp_size_in_bytes / 1e9,
                "temp_per_device_gb": mem.temp_size_in_bytes / 1e9
                / max(1, n_dev),
                "code_gb": mem.generated_code_size_in_bytes / 1e9,
            },
            "xla_cost": {k: cost.get(k, 0.0)
                         for k in ("flops", "bytes accessed")},
            "hlo": hlo,
            "model": {
                "params": cfg.param_count(),
                "params_active": cfg.param_count(active_only=True),
                "seq_len": shape.seq_len,
                "global_batch": shape.global_batch,
            },
        })
    except Exception as e:  # noqa: BLE001 — sweep must survive bad cells
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main():
    # The production meshes need 512 host-platform devices; jax fixes the
    # device count when its backend first initialises, which nothing
    # before this line does.
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in ASSIGNED:
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                cells.append((arch, shape.name))
    else:
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[skip] {tag}", flush=True)
                continue
            print(f"[run ] {tag}", flush=True)
            rec = run_cell(arch, shape_name, mp, smoke=args.smoke)
            path.write_text(json.dumps(rec, indent=1))
            status = "ok" if rec["ok"] else f"FAIL ({rec.get('error')})"
            print(f"[done] {tag}: {status} in {rec['total_s']}s",
                  flush=True)


if __name__ == "__main__":
    main()
