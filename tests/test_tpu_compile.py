"""Compile the simulation kernels for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles one kernel's jitted
wrapper (padding, gathers and the ``pallas_call``) for one chip of a
``v5e:2x2`` topology that is described, not attached, at the widths the
``grid_500`` scenarios reach. What the chip's compiler refuses — an
unsupported gather or slice, a layout it cannot lower, more VMEM than a
kernel may use — fails here, at no chip time.

Widths: 500 sites (5x10x10), 555 links of depth 3, 1 000 files
(``grid_500``) and 10 000 files (``grid_500_evict``), 50-job bursts,
up to 1 250 (job, file) pairs or batch-union files (50 jobs x 25 files),
and the largest slot capacity ``grid_500_saturated`` reaches at 20 000
jobs.

The topology is described inside a module-scoped fixture, so only the
test process that runs this file loads the TPU compiler library.
"""

import functools

import numpy as np
import pytest

SITES, LINKS, LEVELS = 500, 555, 3
FILES, FILES_EVICT = 1_000, 10_000
JOBS, PAIRS = 50, 1_250
SLOTS = 16_384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    import jax
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=np.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    yield make
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *args, pallas=True):
    """Compile ``fn`` for the described chip; a Pallas kernel must reach
    the executable as a Mosaic custom call."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == pallas


@pytest.mark.parametrize("slots", [128, SLOTS])
def test_event_engine_compiles(shape, slots):
    """The flush's device program as the engine's route hands it its
    operands: the padded ``(levels, slots)`` int32 path and the int32
    share ranks behind the sentinel."""
    from repro.kernels.event_engine.kernel import _flush_call, _padded
    s_pad, l_pad = _padded(slots, LEVELS)
    _compile(functools.partial(_flush_call, interpret=False),
             shape((l_pad, s_pad), np.int32), shape((LINKS + 1,), np.int32))


@pytest.mark.parametrize("files", [FILES, FILES_EVICT])
@pytest.mark.parametrize("mode", ["cost", "plain"])
def test_value_score_compiles(shape, files, mode):
    from repro.kernels.value_score.kernel import value_score_kernel
    sf = shape((SITES, files))
    _compile(functools.partial(value_score_kernel, mode=mode), sf,
             shape((files,)), sf, shape((SITES, SITES)))


def test_st_cost_compiles(shape):
    from repro.kernels.st_cost.kernel import st_cost_kernel
    sf = shape((SITES, PAIRS))
    _compile(st_cost_kernel, shape((SITES, SITES)), sf, sf, shape((PAIRS,)),
             shape((JOBS, PAIRS)), shape((SITES,)), shape((SITES,)))


def test_strategy_plan_compiles(shape):
    from repro.kernels.strategy_plan.kernel import strategy_plan_kernel
    sp = shape((SITES, PAIRS))
    _compile(strategy_plan_kernel, sp, sp, sp, shape((SITES,)),
             shape((PAIRS,)), shape((PAIRS,)))


@pytest.mark.parametrize("files", [FILES, FILES_EVICT])
def test_select_sites_batch_compiles(shape, files):
    from repro.core.jaxsched import select_sites_batch
    _compile(select_sites_batch, shape((SITES, files), np.bool_),
             shape((files,)), shape((JOBS, files), np.bool_),
             shape((SITES,)), shape((SITES,)), shape((SITES,), np.bool_),
             pallas=False)
