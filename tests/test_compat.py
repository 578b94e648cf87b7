"""The installed JAX API surface the code base calls directly.

The repo targets one JAX release and calls its entry points without
version shims: ``jax.shard_map`` with the ``check_vma`` keyword
(``core/distributed.py``, ``models/moe.py``) and ``Compiled.cost_analysis``
returning a flat dict (``launch/cells.py``, ``kernels/profiling.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def test_real_shard_map_roundtrip():
    """``jax.shard_map`` with ``check_vma`` drives the installed JAX."""
    mesh = jax.make_mesh((1,), ("x",))
    f = jax.shard_map(
        lambda a: a * 2.0,
        mesh=mesh,
        in_specs=P("x"),
        out_specs=P("x"),
        check_vma=False,
    )
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4.0) * 2.0)


def test_cost_analysis_is_flat_dict():
    compiled = jax.jit(lambda a: a @ a).lower(jnp.ones((8, 8))).compile()
    ca = compiled.cost_analysis()
    assert isinstance(ca, dict)
    assert ca.get("flops", 0) > 0
