"""Ahead-of-time TPU compiles of the three Pallas kernels at paper widths.

The paper's ``paper_train`` setup (batch 1000, fanouts (5, 10, 15), 100
features) pads the input layer to 176,000 destination rows with 5 lanes each
over 1,056,000 source rows (``block_pad_sizes``).  Each test compiles one
kernel at that shape for one chip of a described ``v5e:2x2`` topology: the
TPU compiler checks block tiling, SMEM/VMEM budgets and the operand layouts
that interpret mode never sees.  Nothing runs; no chip is needed.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.minibatch import block_pad_sizes
from repro.kernels.cache_lookup import cache_lookup_agg_pallas
from repro.kernels.gather_agg import gather_agg_pallas
from repro.sampling.kernels import slot_gather_agg_pallas

(DST, SRC), _, _ = block_pad_sizes(1000, (5, 10, 15))   # (176000, 1056000)
K0, FEAT = 5, 100
CACHE_ROWS = 512        # device cache tier: 1% of |V| at scale 0.5, padded


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30, used          # one v5e chip: 16 GB of HBM
    return compiled


def test_slot_gather_compiles_for_v5e(one_chip):
    _compile(slot_gather_agg_pallas, one_chip,
             ((CACHE_ROWS, FEAT), jnp.float32),
             ((DST, K0), jnp.int32), ((DST, K0), jnp.float32))


def test_cache_lookup_compiles_for_v5e(one_chip):
    _compile(cache_lookup_agg_pallas, one_chip,
             ((CACHE_ROWS, FEAT), jnp.float32), ((SRC, FEAT), jnp.float32),
             ((SRC,), jnp.int32), ((DST, K0), jnp.int32),
             ((DST, K0), jnp.float32))


def test_gather_agg_compiles_for_v5e(one_chip):
    _compile(gather_agg_pallas, one_chip,
             ((SRC, FEAT), jnp.float32),
             ((DST, K0), jnp.int32), ((DST, K0), jnp.float32))
