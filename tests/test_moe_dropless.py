"""The held-expert MoE layer in device mode drops nothing, and the crossbar
kernels read and write only each expert's routed rows.

Device mode sizes every held expert's dispatch buffer (and tapes) to the
step's token count, which no routing can overflow: a token picks an
expert at most once.  Each expert's routed row count rides to the
expert-batched read and write, which skip the zero rows past it; a zero
drive integrates zero charge and a zero tape row adds nothing to the
outer product, so the result is the full-capacity kernel's bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tiled_analog import crossbar_from_model
from repro.kernels.xbar_update import xbar_outer_update_inline
from repro.kernels.xbar_vmm import READ_STRIP, xbar_fused_read_inline
from repro.models import moe as MOE

# Rows an expert holds in each case: none, one, one whole read strip, all.
T = 2 * READ_STRIP + 88
COUNTS = (0, 1, READ_STRIP, T)


def _device_cfg(**over):
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    return cfg.replace(dtype="float32", analog=True, analog_mode="device",
                       analog_rows=32, analog_cols=32, **over)


def _skewed(cfg, t: int, targets):
    """A layer and inputs whose router sends every token to ``targets``:
    the inputs are positive, and the router weighs them only into those
    experts' columns."""
    p = MOE.moe_init(jax.random.PRNGKey(0), cfg)
    w = jnp.zeros_like(p["router"]["w"]).at[:, jnp.asarray(targets)].set(
        jnp.linspace(2.0, 1.0, len(targets)))
    p = {**p, "router": {"w": w}}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                  (2, t // 2, cfg.d_model))) + 0.1
    return p, x


def test_skewed_routing_drops_nothing():
    """Every token picks the same held experts: each of them receives all
    T rows, no assignment is lost, and the dropped counter reads 0."""
    cfg = _device_cfg(n_experts=4, n_routed_experts=8, first_expert=2,
                      top_k=2)
    p, x = _skewed(cfg, 32, [3, 4])
    y, stats = MOE.moe_layer(p, x, cfg)
    assert int(stats["moe_dropped"]) == 0
    assert int(stats["moe_routed_rows"]) == 2 * 32
    assert int(stats["max_expert_rows"]) == 32
    assert bool(jnp.all(jnp.isfinite(y)))


def test_dropless_layer_matches_dense_oracle_digitally():
    """With a capacity of every token the digital dispatch equals the
    dense oracle on skewed routing (every held expert's part, gates not
    renormalised, and the shared experts)."""
    cfg = get_config("deepseek-v2-lite-16b", smoke=True).replace(
        dtype="float32", n_experts=4, n_routed_experts=8, first_expert=2,
        top_k=3, capacity_factor=8.0 / 3.0)
    p, x = _skewed(cfg, 32, [3, 4, 6])
    y, stats = MOE.moe_layer(p, x, cfg)
    assert int(stats["moe_dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        MOE.moe_dense_reference(p, x, cfg)), rtol=1e-5, atol=1e-5)


def _rows_case(seed: int):
    cfg = crossbar_from_model(_device_cfg())
    e, k, n = len(COUNTS), 48, 40
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = jnp.asarray(COUNTS, jnp.int32)
    live = (jnp.arange(T)[None, :] < rows[:, None])[..., None]
    x = jax.random.normal(key[0], (e, T, k)) * live
    d = jax.random.normal(key[1], (e, T, n)) * live
    g = jax.random.uniform(key[2], (e, k, n), minval=0.2, maxval=0.8)
    return cfg, rows, x, d, g


@pytest.mark.parametrize("transpose", [False, True], ids=["vmm", "mvm"])
def test_read_with_row_counts_is_bit_identical(transpose):
    """The interpret-mode read that walks only each matrix's counted
    strips equals the read of every capacity row, bit for bit."""
    cfg, rows, x, d, g = _rows_case(0)
    drive = d if transpose else x
    ref = jnp.full_like(g, 0.5)
    ws = jnp.linspace(1.5, 2.5, g.shape[0])
    full = xbar_fused_read_inline(drive, g, ref, ws, cfg,
                                  transpose=transpose, impl="interpret")
    part = xbar_fused_read_inline(drive, g, ref, ws, cfg,
                                  transpose=transpose, impl="interpret",
                                  rows=rows)
    assert bool(jnp.any(full != 0))
    np.testing.assert_array_equal(np.asarray(part), np.asarray(full))


@pytest.mark.parametrize("update_mode", ["outer", "pulse_train"])
def test_write_with_row_counts_is_bit_identical(update_mode):
    """The interpret-mode write that skips each matrix's token blocks past
    its count equals the write over every capacity row, bit for bit,
    kernel noise with a layer offset included."""
    cfg, rows, x, d, g = _rows_case(1)
    scale = jnp.full((g.shape[0],), -0.05)
    kw = dict(seed=jnp.uint32(7), noise_mode="kernel", impl="interpret",
              update_mode=update_mode, tile_offsets=(3, 0, 0))
    full = xbar_outer_update_inline(g, x, d, scale, cfg, **kw)
    part = xbar_outer_update_inline(g, x, d, scale, cfg, rows=rows, **kw)
    assert bool(jnp.any(full != g))
    np.testing.assert_array_equal(np.asarray(part), np.asarray(full))
