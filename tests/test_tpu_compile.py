"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs on a chip here: each test lowers one kernel with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
compiles it with the TPU compiler installed alongside JAX, at the widths
``chip_smoke.py`` runs.  Mosaic's refusals (unsupported shape casts,
unaligned blocks, VMEM overruns) then fail here instead of on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  The module's tests share one xdist group, so
under ``--dist loadgroup`` (or ``loadfile``) one worker loads the library.
The tests skip only where the TPU compiler is not installed; any other
failure to describe the chip fails them.
"""

import importlib.util
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.core import spec_for_space  # noqa: E402
from repro.kernels import interpret_default  # noqa: E402
from repro.kernels.cell_force import ops as cf_ops  # noqa: E402
from repro.kernels.diffusion3d import ops as d3_ops  # noqa: E402
from repro.kernels.pairwise_force import ops as pf_ops  # noqa: E402


pytestmark = pytest.mark.xdist_group("tpu_compile")


def _dims(n_agents: int) -> tuple:
    space = chip_smoke.space_for(n_agents)
    return spec_for_space(0.0, space, chip_smoke.CELL,
                          max_per_cell=chip_smoke.MAX_PER_CELL).dims


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        # A described-chip executable can be written to the persistent
        # cache but not read back without the chip: keep the cache off.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_cell_list_force_compiles(one_chip):
    n, m = chip_smoke.MAIN_AGENTS, chip_smoke.MAX_PER_CELL
    dims = _dims(n)
    n_cells = dims[0] * dims[1] * dims[2]
    compiled = cf_ops.cell_list_force.lower(
        _sds(one_chip, (n, 3), jnp.float32),
        _sds(one_chip, (n,), jnp.float32),
        _sds(one_chip, (n_cells, m), jnp.int32),
        _sds(one_chip, (n,), jnp.int32),
        dims, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_cell_window_force_compiles(one_chip):
    n = chip_smoke.PARITY_AGENTS
    compiled = cf_ops.cell_window_force.lower(
        _sds(one_chip, (n, 3), jnp.float32),
        _sds(one_chip, (n,), jnp.float32),
        _sds(one_chip, (n,), jnp.int32),
        _dims(n), window=-(-n // 128), interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_diffusion3d_compiles(one_chip):
    res = round(chip_smoke.space_for(chip_smoke.MAIN_AGENTS) / chip_smoke.VOXEL)
    compiled = d3_ops.diffusion_step.lower(
        _sds(one_chip, (res, res, res), jnp.float32),
        nu_dt_dx2=0.16, decay_dt=0.002, interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_pairwise_force_compiles(one_chip):
    n, k = chip_smoke.PARITY_AGENTS, 27 * chip_smoke.MAX_PER_CELL
    compiled = pf_ops.pairwise_force.lower(
        _sds(one_chip, (n, 3), jnp.float32),
        _sds(one_chip, (n,), jnp.float32),
        _sds(one_chip, (n, k), jnp.int32),
        _sds(one_chip, (n, k), jnp.bool_),
        interpret=False,
    ).compile()
    _assert_mosaic(compiled)


def test_interpret_default_follows_backend():
    assert interpret_default(True) is True
    assert interpret_default(False) is False
    assert interpret_default() is (jax.default_backend() == "cpu")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert interpret_default() is False
    with mock.patch.object(jax, "default_backend", lambda: "cpu"):
        assert interpret_default() is True
