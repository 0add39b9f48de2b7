"""Compile the served path for a described TPU v5e chip at deployment size.

Nothing runs here: each case lowers one program from shapes alone and
compiles it with the TPU compiler for one chip of a ``v5e:2x2`` topology
that is described, not attached. That catches what the interpret-mode
tests cannot: a kernel the TPU lowering refuses, and a program whose
buffers do not fit the chip's 16 GiB of HBM. The index is the smoke
deployment's (capacity 2^20, 100-d f32, ``M=16, M0=32, L=4,
ef_construction=128``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batch_update import _apply_wave_jit
from repro.core.index import HNSWIndex, HNSWParams
from repro.core.maintenance import consolidate_deletes
from repro.core.planner import exact_scan
from repro.core.search import batch_knn

HBM_BYTES = 16 * 2**30          # one v5e chip
N, D, B, K = 1 << 20, 100, 64, 10
PARAMS = HNSWParams(M=16, M0=32, num_layers=4, ef_construction=128,
                    ef_search=64)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def index(one_chip):
    s = one_chip
    return HNSWIndex(
        vectors=_spec((N, D), jnp.float32, s),
        labels=_spec((N,), jnp.int32, s),
        levels=_spec((N,), jnp.int32, s),
        neighbors=_spec((PARAMS.num_layers, N, PARAMS.M0), jnp.int32, s),
        deleted=_spec((N,), jnp.bool_, s),
        entry=_spec((), jnp.int32, s),
        max_layer=_spec((), jnp.int32, s),
        count=_spec((), jnp.int32, s),
        rng=_spec((2,), jnp.uint32, s))


def _fits(compiled):
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes
    assert used < HBM_BYTES, (
        f"temp {m.temp_size_in_bytes / 2**30:.2f} GiB + arguments "
        f"{m.argument_size_in_bytes / 2**30:.2f} GiB exceed one chip")


def test_exact_scan_kernel_compiles(one_chip, index):
    """The exact tier's topk_dist kernel lowers to Mosaic (no cumsum)."""
    Q = _spec((B, D), jnp.float32, one_chip)
    compiled = exact_scan.lower(PARAMS, index, Q, K,
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_batch_knn_compiles(one_chip, index):
    Q = _spec((B, D), jnp.float32, one_chip)
    _fits(batch_knn.lower(PARAMS, index, Q, K, PARAMS.ef_search).compile())


def test_wave_beam_tier_compiles(one_chip, index):
    """A 256-wide wave on the beam candidate tier, with replace repair:
    the vmapped greedy descent must not broadcast the index per lane."""
    W = 256
    compiled = _apply_wave_jit.lower(
        PARAMS, index, _spec((W,), jnp.int32, one_chip),
        _spec((W,), jnp.int32, one_chip),
        _spec((W, D), jnp.float32, one_chip),
        "mn_ru_gamma", True, True, "beam").compile()
    _fits(compiled)


def test_consolidate_deletes_compiles(index):
    """Consolidation re-prunes in row blocks, not all N pools at once."""
    _fits(consolidate_deletes.lower(PARAMS, index).compile())


@pytest.mark.parametrize("program", ["index_health", "repair_unreachable"])
def test_maintenance_consult_compiles(one_chip, index, program):
    """The health sweep, and the repair pass that takes its mask: one
    program each, under the names the maintenance layer's device time is
    read by."""
    from repro.core.maintenance import _repair, index_health
    if program == "index_health":
        lowered = index_health.lower(index)
    else:
        lowered = _repair.lower(PARAMS, index,
                                _spec((N,), jnp.bool_, one_chip))
    compiled = lowered.compile()
    assert compiled.as_text().startswith(f"HloModule jit_{program}")
    _fits(compiled)
