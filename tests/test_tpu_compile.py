"""Ahead-of-time compiles of the simulator's main path for a TPU v5e.

The TPU compiler is installed, so a chip that is described (not attached)
still refuses what the chip would: block shapes off the (8, 128) tiling,
ops Mosaic cannot lower, programs that do not fit.  These tests compile
the tiled one-hot tick kernel under the grid's lane ``vmap`` at Table-1
and 512-host widths, and the staged XLA grid at Table-1 width.  Nothing
runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.common import build_scenario, knob_grid, sweep_axes_for
from repro.core.netsim import simulator as sim
from repro.core.netsim.params import grid_from_params
from repro.core.netsim.stages import init_state, make_ctx, stage_starts
from repro.kernels.netsim_tick import fused_tick

LANES = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding, lanes=None):
    lead = () if lanes is None else (lanes,)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + tuple(x.shape), x.dtype,
                                       sharding=sharding), tree)


def _compile_tick(one_chip, scenario, segsum="onehot", blk=256, **over):
    """Compile one fused tick for ``LANES`` vmapped lanes of a scenario,
    each lane with its own static arrays and engine state."""
    built = build_scenario(scenario, **over)
    cfg = built.cfg
    st = sim.build_static(built.topo, built.wl, "ecmp", 0, dt=cfg.dt,
                          deploy=cfg.deploy)
    wla = sim.wl_arrays(built.wl, cfg.dt)

    def view(st):
        ctx = make_ctx(st, wla, cfg.window)
        state = init_state(ctx, jax.random.PRNGKey(0))
        return ctx, state

    def tick(st, starts, state, t):
        ctx, _ = view(st)
        return fused_tick(ctx, cfg, starts, state, t, segsum=segsum,
                          blk=blk, interpret=False)

    def starts_state(st):
        ctx, state = view(st)
        return stage_starts(ctx, state, 0), state

    starts, state = jax.eval_shape(starts_state, st)
    args = (_specs(st, one_chip, LANES), _specs(starts, one_chip, LANES),
            _specs(state, one_chip, LANES),
            jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip))
    return jax.jit(jax.vmap(tick)).lower(*args).compile()


def test_tiled_kernel_compiles_table1_width(one_chip):
    compiled = _compile_tick(one_chip, "table1_ring")
    assert "tpu_custom_call" in compiled.as_text()


def test_tiled_kernel_compiles_512_host_width(one_chip):
    compiled = _compile_tick(one_chip, "fat_tree_multipod", n_hosts=512)
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_grid_compiles_table1(one_chip):
    built = build_scenario("table1_ring")
    cfgs = knob_grid(built.cfg._replace(n_ticks=40),
                     sweep_axes_for("table1_ring"))[:LANES]
    struct, knobs = grid_from_params(cfgs)
    stacked, keys = sim._stacked_statics(built.topo, built.wl, "ecmp", (0,),
                                         struct)
    wla = sim.wl_arrays(built.wl, struct.dt)
    compiled = sim._grid_core.lower(
        _specs(stacked, one_chip), _specs(wla, one_chip), struct,
        _specs(knobs, one_chip), _specs(keys, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.parametrize("variant", [
    dict(segsum="scatter", blk=None), dict(segsum="onehot", blk=None),
    dict(segsum="onehot", blk=200)])
def test_unlowerable_variant_raises(one_chip, variant):
    with pytest.raises(ValueError, match="blk=256"):
        _compile_tick(one_chip, "table1_ring", **variant)
