"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: Mosaic then refuses what interpret mode accepts
(unsupported lowerings, misaligned slices, too much VMEM).  Shapes come
from the real batched programming pipeline (`program_packed` under
`jax.eval_shape`), so the cases follow the plans the serving path builds.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and under several test workers
only the worker that runs this file loads it.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import blockamc
from repro.core.analog import AnalogConfig
from repro.core.nonideal import PAPER_FULL
from repro.kernels import ops

# (n, array_size, stages): the paper plan (Fig. 8: T=23 tiles of 64^2,
# arena S=704), the 512^2 plan on 128^2 arrays, and a four-stage 1024^2
# plan on 64^2 arrays (T=431).
PLANS = {"paper_256": (256, 64, 2), "n512_a128": (512, 128, 2),
         "n1024_4stage": (1024, 64, 4)}


@pytest.fixture(scope="module")
def one_chip():
    topologies = pytest.importorskip("jax.experimental.topologies")
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@pytest.mark.parametrize("plan,m", [("paper_256", 1), ("paper_256", 16),
                                    ("n512_a128", 16), ("n1024_4stage", 16)])
def test_arena_packed_apply_compiles(one_chip, plan, m):
    n, array_size, stages = PLANS[plan]
    cfg = AnalogConfig(array_size=array_size, nonideal=PAPER_FULL)
    pp = jax.eval_shape(partial(blockamc.program_packed, cfg=cfg,
                                stages=stages),
                        jax.ShapeDtypeStruct((m, n, n), jnp.float32),
                        jax.ShapeDtypeStruct((m, 2), jnp.uint32))
    if plan == "paper_256":
        assert pp.program_ops.shape[1:] == (23, 64, 64)
        assert pp.arena_size == 704
    arena = jax.ShapeDtypeStruct((m, pp.arena_size, 4), jnp.float32,
                                 sharding=one_chip)
    meta = [_sds(x, one_chip) for x in pp.program_meta]
    compiled = jax.jit(partial(ops.arena_packed_apply, interpret=False)).lower(
        arena, _sds(pp.program_ops, one_chip), *meta).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_selected_packed_executor_compiles(one_chip, monkeypatch):
    """The served flush's executor at the paper's fleet: 5 tenants
    gathered by index from a 16-tenant resident stack and run through
    the packed megakernel, as one program."""
    n, array_size, stages = PLANS["paper_256"]
    cfg = AnalogConfig(array_size=array_size, nonideal=PAPER_FULL)
    pp = jax.eval_shape(partial(blockamc.program_packed, cfg=cfg,
                                stages=stages),
                        jax.ShapeDtypeStruct((16, n, n), jnp.float32),
                        jax.ShapeDtypeStruct((16, 2), jnp.uint32))
    pp = jax.tree_util.tree_map(lambda x: _sds(x, one_chip), pp)
    idx = jax.ShapeDtypeStruct((5,), jnp.int32, sharding=one_chip)
    bs = jax.ShapeDtypeStruct((5, n, 8), jnp.float32, sharding=one_chip)
    # the executor picks the kernel from the default backend (the CPU
    # here); a fresh jit keeps this trace out of the served one's cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(blockamc.execute_arena_packed_selected,
                       donate_argnums=(2,)).lower(pp, idx, bs).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (batch, block rows, block size, rhs width) before ops.py pads s and k to
# 128; (2, 64, 64, 64) is the nodal oracle's effective-conductance solve
# of a 64^2 paper array, past the default scoped VMEM limit.
@pytest.mark.parametrize("b,nr,s,k", [(4, 8, 128, 128), (2, 64, 64, 64)])
def test_block_tridiag_solve_compiles(one_chip, b, nr, s, k):
    minv = jax.ShapeDtypeStruct((b, nr, s, s), jnp.float32, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((b, nr, s, k), jnp.float32, sharding=one_chip)
    compiled = jax.jit(partial(ops.block_tridiag_solve, gw=100.0,
                               interpret=False)).lower(minv, rhs).compile()
    assert "tpu_custom_call" in compiled.as_text()
