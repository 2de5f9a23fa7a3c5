"""Sharded analog training: bit-exact parity with the single-device step,
shard-invariant counter PRNG, and the tile-granular container specs.

The parity tests run in subprocesses (host-platform device-count trick) so
the main pytest process keeps seeing one device, per the dry-run contract.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tiled_analog import crossbar_from_model

REPO = Path(__file__).resolve().parent.parent


def _run(script: str, env_extra=None, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


def _cfg(**kw):
    base = dict(dtype="float32", analog=True, analog_mode="device",
                analog_device="taox", analog_rows=16, analog_cols=16,
                analog_in_bits=8, analog_out_bits=8)
    base.update(kw)
    return get_config("lm100m", smoke=True).replace(**base)


# ------------------------------------------------------- PRNG shard-invariance

def test_field_normals_offsets_match_global_slices():
    """A shard holding tile block (l0:, k0:, n0:) with tile_offsets set
    must generate exactly the corresponding slice of the global noise
    field — the invariance behind one-seed-any-mesh reproducibility."""
    from repro.kernels.xbar_update import field_normals
    cfg = crossbar_from_model(_cfg())
    rows, cols = cfg.rows, cfg.cols
    seed = jnp.uint32(1234)
    full = field_normals(seed, (4, 4 * rows, 4 * cols), cfg)
    # block of layers 2:4, row-tiles 1:3, col-tiles 2:4
    part = field_normals(seed, (2, 2 * rows, 2 * cols), cfg,
                         tile_offsets=(2, 1, 2))
    np.testing.assert_array_equal(
        part, full[2:4, rows:3 * rows, 2 * cols:4 * cols])


def test_update_block_with_offsets_matches_slice_of_full():
    """The invariant the sharded step runs on: updating one shard's tile
    block with its global base coordinates as ``tile_offsets`` produces
    exactly the corresponding block of the whole-array update (same impl,
    noise included).  Bitwise."""
    from repro.kernels.xbar_update import xbar_outer_update
    cfg = crossbar_from_model(_cfg())
    rows, cols = cfg.rows, cfg.cols
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    g = 0.5 + 0.1 * jax.random.uniform(k[0], (2, 4 * rows, 4 * cols))
    x_q = jax.random.normal(k[1], (2, 8, 4 * rows))
    d_q = jax.random.normal(k[2], (2, 8, 4 * cols))
    seed = jnp.uint32(7)
    full = xbar_outer_update(g, x_q, d_q, 1e-3, cfg, seed=seed,
                             noise_mode="kernel", impl="fused")
    # shard owning row-tiles 2:4, col-tiles 1:3
    kr = slice(2 * rows, 4 * rows)
    nc = slice(1 * cols, 3 * cols)
    block = xbar_outer_update(g[:, kr, nc], x_q[..., kr], d_q[..., nc],
                              1e-3, cfg, seed=seed, noise_mode="kernel",
                              impl="fused", tile_offsets=(0, 2, 1))
    np.testing.assert_array_equal(block, full[:, kr, nc])
    # offsets actually shift the PRNG stream
    base = xbar_outer_update(g[:, kr, nc], x_q[..., kr], d_q[..., nc],
                             1e-3, cfg, seed=seed, noise_mode="kernel",
                             impl="fused")
    assert float(jnp.max(jnp.abs(base - block))) > 0


def test_update_tile_offsets_agree_across_impls():
    """interpret (the oracle) and fused agree to float tolerance for the
    same seed AND the same tile offsets (same contract as the
    offset-free agreement test in test_update_fusion.py)."""
    from repro.kernels.xbar_update import xbar_outer_update
    cfg = crossbar_from_model(_cfg())
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    g = 0.5 + 0.1 * jax.random.uniform(k[0], (2, 32, 64))
    x_q = jax.random.normal(k[1], (2, 8, 32))
    d_q = jax.random.normal(k[2], (2, 8, 64))
    outs = [xbar_outer_update(g, x_q, d_q, 1e-3, cfg, seed=jnp.uint32(7),
                              noise_mode="kernel", impl=impl,
                              tile_offsets=(3, 5, 9))
            for impl in ("interpret", "fused")]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- container specs

def test_analog_container_specs_policy():
    """Tile-granular split: producers (dp-rows, model-cols), consumers
    flipped, w_scale replicated, degradation to replication when the dim
    doesn't divide at whole-tile granularity."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import (analog_container_pspec,
                                       analog_update_specs)
    cfg = _cfg()

    class FakeMesh:
        shape = {"data": 2, "model": 4}
        axis_names = ("data", "model")
    mesh = FakeMesh()
    # producer, divisible everywhere: (L, K, N) = (2, 64, 256), 16x16 tiles
    sp = ["layers", "attn", "wqkv", "g"]
    assert analog_container_pspec(sp, (2, 64, 256), cfg, mesh, "g") \
        == P(None, "data", "model")
    # consumer orientation flips
    sp_wo = ["layers", "attn", "wo", "g"]
    assert analog_container_pspec(sp_wo, (2, 64, 64), cfg, mesh, "g") \
        == P(None, "model", "data")
    # non-divisible at tile granularity -> replicate that dim
    assert analog_container_pspec(sp, (2, 48, 96), cfg, mesh, "g") \
        == P(None, None, None)
    # w_scale follows its container's lead dims; tapes follow their
    # container
    specs = analog_update_specs(("layers", "attn", "wqkv"), (2, 64, 256),
                                cfg, mesh)
    assert specs["scale"] == P(None)
    assert specs["x_tape"] == P(None, None, "data")
    assert specs["d_tape"] == P(None, None, "model")
    # expert-batched containers: expert dim over model (EP), row tiles
    # over the FSDP axes, columns replicated, per-expert scales with
    # their experts
    sp_e = ["layers", "moe", "experts", "w_up", "g"]
    assert analog_container_pspec(sp_e, (2, 8, 64, 64), cfg, mesh, "g") \
        == P(None, "model", "data", None)
    especs = analog_update_specs(("layers", "moe", "experts", "w_up"),
                                 (2, 8, 64, 64), cfg, mesh)
    assert especs["x_tape"] == P(None, "model", None, "data")
    assert especs["d_tape"] == P(None, "model", None, None)
    assert especs["scale"] == P(None, "model")
    # an expert count that doesn't divide the model axis degrades
    assert analog_container_pspec(sp_e, (2, 6, 64, 64), cfg, mesh, "g") \
        == P(None, None, "data", None)


# ----------------------------------------------------- sharded-vs-single parity

_PARITY_SCRIPT = """
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%(devices)r")
    import jax, jax.numpy as jnp, numpy as np
    import jax.tree_util as jtu
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.train.analog_lm import init_state, make_analog_sgd_step

    cfg = get_config(%(arch)r, smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_device="taox", analog_rows=%(rows)r, analog_cols=%(rows)r,
        analog_in_bits=8, analog_out_bits=8, **%(extra)r)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                   jnp.int32)}
    keys = [jax.random.PRNGKey(i) for i in range(4)]

    # reference: plain single-device step (no mesh machinery at all)
    state = init_state(jax.random.PRNGKey(0), cfg)
    step1 = make_analog_sgd_step(cfg, lr=0.05)
    for k in keys:
        state, m1 = step1(state, batch, k)

    mesh = make_mesh(%(shape)r, ("data", "model"))
    step = make_analog_sgd_step(cfg, lr=0.05, mesh=mesh)
    st = step.shard_state(init_state(jax.random.PRNGKey(0), cfg))
    for k in keys:
        st, m = step(st, batch, k)

    assert step.compiles == 1, step.compiles
    # the probed container must actually live sharded on the mesh
    g = st["params"]%(leaf)s["g"]
    assert not g.sharding.is_fully_replicated, g.sharding
    # bit-identical conductances AND digital leaves after 4 noisy steps
    same = jtu.tree_map(lambda a, b: bool(jnp.all(a == b)),
                        state["params"], st["params"])
    bad = [jtu.keystr(p) for p, v in jtu.tree_flatten_with_path(same)[0]
           if not v]
    assert not bad, bad
    assert float(m1["loss"]) == float(m["loss"])
    assert float(m1["g_rail_frac"]) == float(m["g_rail_frac"])
    print("PARITY_OK")
"""


def _parity(arch, shape, rows, leaf, extra=None):
    devices = int(np.prod(shape))
    return textwrap.dedent(_PARITY_SCRIPT % {
        "arch": arch, "shape": shape, "rows": rows, "leaf": leaf,
        "devices": devices, "extra": dict(extra or {})})


def test_sharded_step_bit_identical_2x4():
    """Acceptance: same seed, 1 device vs a 2x4 mesh -> bit-identical
    conductance containers after 4 steps of the stochastic taox device,
    with the jitted sharded step compiling exactly once."""
    r = _run(_parity("lm100m", (2, 4), 16,
                     '["layers"]["ffn"]["w_upgate"]'))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_step_bit_identical_8x1():
    """Mesh-shape invariance: the pure-FSDP 8x1 layout (row tiles only —
    8x8 physical tiles so the 64-wide smoke projections split 8 ways)
    produces the same bits as 1 device too."""
    r = _run(_parity("lm100m", (8, 1), 8,
                     '["layers"]["ffn"]["w_upgate"]'))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_step_bit_identical_1x8():
    """Pure tensor-parallel 1x8 layout (column tiles only — 8x8 physical
    tiles so the smoke projections' output dims split 8 ways).  The
    manual-collective read's output gather and the flipped consumer
    orientation both get exercised with no FSDP axis to hide behind."""
    r = _run(_parity("lm100m", (1, 8), 8,
                     '["layers"]["ffn"]["w_upgate"]'))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_step_bit_identical_4x4_16way():
    """16-way acceptance leg: a 4x4 mesh splits row AND column tiles of
    every projection 4 ways each (8x8 physical tiles).  Same-seed
    bit-identity must hold at the largest CI mesh, where the ordered
    partial-sum combine spans 4 reduction shards."""
    r = _run(_parity("lm100m", (4, 4), 8,
                     '["layers"]["ffn"]["w_upgate"]'))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


_MOE_EP_SCRIPT = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import jax.tree_util as jtu
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.train.analog_lm import init_state, make_analog_sgd_step

    cfg = get_config("llama4-scout-17b-a16e", smoke=True).replace(
        dtype="float32", analog=True, analog_mode="device",
        analog_device="taox", analog_rows=16, analog_cols=16,
        analog_in_bits=8, analog_out_bits=8)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                   jnp.int32)}
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    mesh = make_mesh((2, 4), ("data", "model"))

    losses = {}
    states = {}
    for mode in ("local", "gather"):
        step = make_analog_sgd_step(cfg, lr=0.05, mesh=mesh,
                                    read_mode=mode)
        st = step.shard_state(init_state(jax.random.PRNGKey(0), cfg))
        ls = []
        for k in keys:
            st, m = step(st, batch, k)
            ls.append(float(m["loss"]))
        assert step.compiles == 1, (mode, step.compiles)
        losses[mode] = ls
        states[mode] = st
    # the EP dispatch read must match the gather-everything read
    # token-for-token: identical per-step losses (every token's logits
    # fed the same cross-entropy) and a bit-identical tree after 4
    # noisy steps, expert containers included.
    assert losses["local"] == losses["gather"], losses
    same = jtu.tree_map(lambda a, b: bool(jnp.all(a == b)),
                        states["local"]["params"],
                        states["gather"]["params"])
    bad = [jtu.keystr(p) for p, v in jtu.tree_flatten_with_path(same)[0]
           if not v]
    assert not bad, bad
    g = states["local"]["params"]["layers"]["moe"]["experts"]["w_upgate"]["g"]
    assert not g.sharding.is_fully_replicated, g.sharding
    print("EP_PARITY_OK")
"""


def test_moe_ep_dispatch_read_matches_gather_path():
    """The capacity-aware EP read (each shard reads only its own experts'
    tiles of the replicated dispatch buffer) must be indistinguishable
    from the legacy gather-everything read: token-for-token equal losses
    and bit-identical conductances after 4 noisy steps on a 2x4 mesh.
    Both modes also satisfy the single-device parity contract, so this
    pins the A/B pair to each other AND to the 1-device program."""
    r = _run(textwrap.dedent(_MOE_EP_SCRIPT))
    assert "EP_PARITY_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_step_bit_identical_moe_2x4():
    """Expert-sharded containers keep the contract: the llama4 smoke MoE
    on a 2x4 mesh — expert dim over ``model`` (4-way EP, 2 experts per
    shard), expert row tiles over ``data`` — produces bit-identical
    conductances to 1 device, probed on an expert container."""
    r = _run(_parity("llama4-scout-17b-a16e", (2, 4), 16,
                     '["layers"]["moe"]["experts"]["w_upgate"]'))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sharded_step_bit_identical_carry_2x4():
    """Acceptance: the same-seed sharded==unsharded bit-parity contract
    extends over periodic carry.  4 noisy steps with carry_period=2 fire
    two serial carry sweeps inside the donated step on a 2x4 mesh; every
    leaf — primary conductances AND the carry LSB arrays (sharded
    identically, folded shard-locally) — stays bit-identical to the
    single-device run, and the jit still compiles exactly once."""
    r = _run(_parity("lm100m", (2, 4), 16,
                     '["layers"]["ffn"]["w_upgate"]',
                     extra=dict(analog_carry=True, carry_period=2,
                                analog_carry_base=4.0)))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_sharded_step_bit_identical_pulse_train_2x4():
    """Pulse-train updates keep the contract too: the sign-decomposed
    4-phase write uses the same shard-invariant counter-PRNG streams, so
    integer event counts and write noise reproduce on any mesh."""
    r = _run(_parity("lm100m", (2, 4), 16,
                     '["layers"]["ffn"]["w_upgate"]',
                     extra=dict(analog_update_mode="pulse_train")))
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr
