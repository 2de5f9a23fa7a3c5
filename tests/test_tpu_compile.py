"""The main path's Pallas kernels compile for a TPU v5e (Mosaic).

Interpret mode never lowers a kernel through Mosaic, so it cannot see
what the TPU compiler refuses: unaligned blocks, scoped-VMEM overruns,
casts it has no lowering for, bodies too large to compile in time.  These
tests compile each kernel of the analog training step — and the whole
step — for a *described* v5e (no chip attached) at lm100m's published
widths on the paper's 1024x1024 tiles, with the token batch of
``chip_smoke.py`` (8 x 256).  Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and a
module that loads it while being collected would give each test worker a
different set of tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.tiled_analog import crossbar_from_model
from repro.kernels.xbar_update import xbar_outer_update_inline
from repro.kernels.xbar_vmm import (fakequant_read_pallas,
                                    xbar_fused_read_inline)
from repro.train.analog_lm import init_state, make_analog_sgd_step

TOKENS = 8 * 256
CFG = get_config("lm100m").replace(dtype="float32", analog=True,
                                   analog_mode="device")
XCFG = crossbar_from_model(CFG)          # 1024x1024 tiles, taox, 8/8 bits
L, K, N = CFG.n_layers, CFG.d_model, 3 * CFG.d_model   # the wqkv stack
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile can be written to the persistent cache
    # but never read back without a chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("transpose", [False, True], ids=["vmm", "mvm"])
def test_fused_read_compiles(one_chip, transpose):
    s = lambda *shape: _spec(one_chip, shape)
    drive = N if transpose else K
    c = _compile(lambda x, g, r, w: xbar_fused_read_inline(
        x, g, r, w, XCFG, transpose=transpose, impl="pallas"),
        s(L, TOKENS, drive), s(L, K, N), s(L, K, N), s(L))
    assert KERNEL in c.as_text()


@pytest.mark.parametrize("noise_mode", ["kernel", "none"])
@pytest.mark.parametrize("update_mode", ["outer", "pulse_train"])
def test_update_compiles(one_chip, update_mode, noise_mode):
    """The kernel-noise cases generate the epilogue's normals in-kernel
    (counter PRNG + Box–Muller) over a whole 1024x1024 tile."""
    s = lambda *shape: _spec(one_chip, shape)
    c = _compile(lambda g, x, d, m, seed: xbar_outer_update_inline(
        g, x, d, m, XCFG, seed=seed if noise_mode == "kernel" else None,
        noise_mode=noise_mode, impl="pallas", update_mode=update_mode),
        s(L, K, N), s(L, TOKENS, K), s(L, TOKENS, N), s(L),
        _spec(one_chip, (), jnp.uint32))
    assert KERNEL in c.as_text()


def test_fakequant_read_compiles(one_chip):
    s = lambda *shape: _spec(one_chip, shape)
    c = _compile(lambda x, w: fakequant_read_pallas(x, w, XCFG.adc,
                                                    rows=XCFG.rows),
                 s(TOKENS, K), s(K, N))
    assert KERNEL in c.as_text()


def test_fused_read_refuses_a_block_beyond_vmem(one_chip):
    """The dynamic ADC range is calibrated over one token block, so the
    read holds the whole batch in VMEM; past the cap it says so instead
    of failing inside the compiler."""
    s = lambda *shape: _spec(one_chip, shape)
    with pytest.raises(ValueError, match="VMEM"):
        _compile(lambda x, g, r, w: xbar_fused_read_inline(
            x, g, r, w, XCFG, impl="pallas"),
            s(4 * TOKENS, K), s(K, N), s(K, N), s())


def test_train_step_compiles_with_kernels(one_chip):
    """The whole full-width analog step on one chip: every container's
    read and write is a Mosaic kernel, and the program fits the v5e's
    16 GB of HBM."""
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: init_state(
                             jax.random.PRNGKey(0), CFG)))
    tok = _spec(one_chip, (8, 256), jnp.int32)
    key = _spec(one_chip, (2,), jnp.uint32)
    step = make_analog_sgd_step(CFG, lr=0.05, impl="pallas",
                                read_impl="pallas")
    c = step._step.lower(state, {"tokens": tok, "labels": tok},
                         key).compile()
    # At least a VMM, an MVM and a write for each of the 4 containers.
    assert c.as_text().count(KERNEL) >= 12
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9
