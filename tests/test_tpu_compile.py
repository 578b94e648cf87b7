"""Compile guards: every RMQ kernel compiles for a described TPU v5e chip.

Nothing runs here.  Each test lowers a kernel's jitted wrapper with
``interpret=False`` for one device of a described v5e topology, at the
size the chip smoke serves (n = 2^26, c = 128, t = 64), and compiles it
with the TPU compiler — which refuses what the chip would refuse: slices
not aligned to the HBM tiling, VMEM overruns, lowerings Mosaic lacks.
Interpret-mode parity lives in ``test_kernels.py`` / ``test_fused_build.py``.

The topology is described inside a module-scoped fixture (never at import
or collection time): only one process may hold the TPU compiler library,
and every test worker collects this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.hierarchy import Hierarchy
from repro.core.plan import make_plan
from repro.kernels import common

N = 1 << 26
M = 4096  # queries (and update points) per compiled batch
QB = common.DEFAULT_QUERY_BLOCK


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hierarchy(sharding, plan, pos):
    return Hierarchy(
        base=_sds(sharding, (plan.capacity,), jnp.float32),
        upper=_sds(sharding, (plan.upper_size,), jnp.float32),
        upper_pos=(_sds(sharding, (plan.upper_size,), jnp.int32)
                   if pos else None),
        plan=plan,
    )


def _lower(name, sharding, n, pos, c=128):
    """The kernel's jitted wrapper, lowered for the described chip."""
    plan = make_plan(n, c=c, t=64)
    ls = _sds(sharding, (M,), jnp.int32)
    if name == "hierarchy_fused":
        from repro.kernels.hierarchy_fused import ops
        return ops._fused_jit.lower(
            _sds(sharding, (n,), jnp.float32), plan, pos, False)
    if name == "hierarchy_build":
        from repro.kernels.hierarchy_build import ops
        return ops._build_jit.lower(
            _sds(sharding, (n,), jnp.float32), plan, pos, False)
    h = _hierarchy(sharding, plan, pos)
    if name == "hierarchy_update":
        from repro.kernels.hierarchy_update import ops
        return ops._update_jit.lower(
            h, ls, _sds(sharding, (M,), jnp.float32), False)
    if name == "rmq_short":
        from repro.kernels.rmq_short import ops
        return ops._run_rmq_short.lower(h.base, ls, ls, plan, QB, pos, False)
    if name == "rmq_scan":
        from repro.kernels.rmq_scan import ops
        fn = ops._run_rmq_scan
    elif name == "rmq_fused":
        from repro.kernels.rmq_fused import ops
        fn = ops._run_rmq_fused
    else:
        from repro.kernels.rmq_bulk import ops
        fn = ops._run_rmq_bulk
    return fn.lower(h.base, h.upper, h.upper_pos, ls, ls, plan, QB, pos,
                    False)


def _compile(name, sharding, n, pos, c=128):
    compiled = _lower(name, sharding, n, pos, c).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = ["hierarchy_fused", "hierarchy_build", "hierarchy_update",
           "rmq_short", "rmq_scan", "rmq_fused", "rmq_bulk"]
QUERY_KERNELS = ["rmq_short", "rmq_scan", "rmq_fused", "rmq_bulk"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    """Position-tracking variant (level 0 positions for rmq_short)."""
    _compile(name, one_chip, N, pos=True)


@pytest.mark.parametrize("name", QUERY_KERNELS)
def test_query_kernel_value_only_compiles(one_chip, name):
    _compile(name, one_chip, N, pos=False)


@pytest.mark.parametrize("logn,fits", [(27, True), (28, False)])
def test_query_vmem_guard_matches_compiler(one_chip, logn, fits):
    """The walk kernels' guard refuses exactly what the compiler does:
    with positions, the resident upper buffer fits at 2^27, not 2^28."""
    plan = make_plan(1 << logn, c=128, t=64)
    if fits:
        common.check_query_vmem(plan, track_pos=True)
        _compile("rmq_fused", one_chip, 1 << logn, pos=True)
    else:
        with pytest.raises(ValueError, match="VMEM"):
            common.check_query_vmem(plan, track_pos=True)
        with pytest.raises(Exception, match="vmem"):
            _compile("rmq_fused", one_chip, 1 << logn, pos=True)


@pytest.mark.parametrize("n,pos,fits", [
    (75_808_768, True, True), (76_062_720, True, False),
    (113_606_656, False, True), (114_184_192, False, False),
])
def test_fused_build_vmem_guard_matches_compiler(one_chip, n, pos, fits):
    """At c=32 the fused build's resident planes cross the measured
    budget between each pair of sizes; the guard and the compiler agree."""
    from repro.kernels.hierarchy_fused import ops

    plan = make_plan(n, c=32, t=64)
    need = ops.vmem_bytes(plan, pos)
    assert (need <= ops.VMEM_BUDGET_BYTES[pos]) == fits
    if fits:
        _compile("hierarchy_fused", one_chip, n, pos=pos, c=32)
    else:
        with pytest.raises(Exception, match="vmem"):
            _compile("hierarchy_fused", one_chip, n, pos=pos, c=32)
