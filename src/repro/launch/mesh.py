"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because smoke
tests and benchmarks must see exactly one CPU device, while
``launch/dryrun.py`` sets the 512-placeholder-device XLA flag before its
first jax import and then calls this.

Axes:
* single pod:  (16, 16)      ("data", "model")  — 256 chips (one v5e pod)
* multi-pod:   (2, 16, 16)   ("pod", "data", "model") — 512 chips

``pod`` and ``data`` carry data parallelism + FSDP weight sharding;
``model`` carries tensor / sequence / expert parallelism.  At >2 pods the
same function takes ``num_pods``; the mesh ladder for degraded (elastic)
configurations lives in ``repro.distributed.fault_tolerance``.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False, num_pods: int = 2):
    shape = (num_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU fake-device tests (same axis semantics)."""
    return _make_mesh(shape, axes)
